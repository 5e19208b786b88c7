"""chip_smoke.py off the chip: it refuses to report a result without a
TPU (or without the repo beside it), and its one-chip phases run end to
end at reduced width on the CPU, Pallas kernels in interpret mode."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import reduced  # noqa: E402

SMALL = chip_smoke.Plan(requests=4, pages=(4, 12), max_new=4, max_len=64)


def _ok_line(text: str) -> bool:
    lines = text.strip().splitlines()
    if not lines:
        return False
    try:
        return json.loads(lines[-1]).get("ok") is True
    except ValueError:
        return False


def test_refuses_cpu(capsys):
    try:
        rc = chip_smoke.main([])
    except RuntimeError:        # a TPU was expected and JAX is on the CPU
        rc = 1
    assert rc != 0
    assert not _ok_line(capsys.readouterr().out)


def test_fails_alone(tmp_path):
    """Copied into a directory with nothing else of the repo, the script
    exits non-zero and prints no result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert not _ok_line(out.stdout)


def test_prompts_are_whole_pages():
    cfg = reduced(get_config("qwen2-1.5b"))
    prompts = chip_smoke.prompts_for(cfg, SMALL, seed=0)
    ps = cfg.hdp.block_k
    assert len(prompts) == SMALL.requests
    assert all(4 * ps <= len(p) <= 12 * ps for p in prompts)
    assert prompts == chip_smoke.prompts_for(cfg, SMALL, seed=0)


def test_one_chip_phases_reduced(capsys):
    out = chip_smoke.one_chip(reduced(get_config("qwen2-1.5b")), SMALL,
                              expect_decode="paged_hdp_decode")
    assert out["decode_rel_l2"] <= chip_smoke.DECODE_REL_TOL
    assert 0.0 <= out["token_agreement"] <= 1.0
    log = capsys.readouterr().out
    assert "attn_decode: paged_hdp_decode" in log
    assert "decode check layer 1" in log


def test_one_chip_rejects_wrong_decode_backend():
    with pytest.raises(AssertionError, match="decode resolved to"):
        chip_smoke.one_chip(reduced(get_config("qwen2-1.5b")), SMALL,
                            expect_decode="pallas_paged_decode")
