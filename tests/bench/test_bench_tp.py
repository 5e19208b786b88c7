"""A four-chip cell on four CPU devices: one tensor-parallel engine, the
weights made in the program's layout, the reference on one device.

Everything runs in one child process (this file run as a script) with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the tests read
what it reports:

* ``make_params`` on a 4-way mesh gives, leaf by leaf, the bits of the
  unsharded call, and lays each matrix out on its head, kv-head, MLP or
  vocabulary axis on ``model`` (norms replicated); so too for Llama's
  o and MLP biases (``data/tiny_llama.json``);
* the reference reads the sharded weights as it reads the unsharded ones;
* ``bench/run.run`` on ``data/tiny_tp4.json`` (8 q / 4 kv heads, chips 4)
  serves with an engine of ``tp`` 4 whose pool is head-sharded, comes out
  correct, and comes out not correct with a served token altered.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
#: the child's time limit (s)
TIMEOUT_S = 180
SEED = 2**31 + 5
#: leaf -> the axis the program's tensor-parallel layout puts on "model"
#: (layers stacked on axis 0); leaves not named are replicated
SPLIT = {
    "['embed']['tok']": 0,
    "['layers']['attn']['wq']": 2, "['layers']['attn']['wk']": 2,
    "['layers']['attn']['wv']": 2, "['layers']['attn']['wo']": 1,
    "['layers']['attn']['bq']": 1, "['layers']['attn']['bk']": 1,
    "['layers']['attn']['bv']": 1,
    "['layers']['ffn']['w_gate']": 2, "['layers']['ffn']['w_up']": 2,
    "['layers']['ffn']['w_down']": 1,
}
#: the same for each case the child makes: Llama's o and MLP biases
#: beside them, the MLP width's on "model"
SPLITS = {"float32": SPLIT, "bfloat16": SPLIT, "llama": dict(SPLIT, **{
    "['layers']['attn']['bo']": None, "['layers']['ffn']['b_gate']": 1,
    "['layers']['ffn']['b_up']": 1, "['layers']['ffn']['b_down']": None})}


def _child() -> dict:
    import jax
    import numpy as np

    from bench import run as R
    from bench.reference.model import served_logits
    from bench.weights import make_params
    from conftest import tiny_runner
    from repro.launch.mesh import make_serving_mesh
    from test_bench_faults import altered_token

    out = {"devices": len(jax.devices())}
    mesh = make_serving_mesh(tp=4, devices=jax.devices()[:4])
    cfg = json.loads((HERE / "data/tiny_tp4.json").read_text())
    llama = json.loads((HERE / "data/tiny_llama.json").read_text())
    cases = {"float32": cfg, "bfloat16": dict(cfg, torch_dtype="bfloat16"),
             "llama": llama}
    for case, c in cases.items():
        one = jax.tree_util.tree_flatten_with_path(make_params(c, SEED))[0]
        four = jax.tree_util.tree_flatten_with_path(make_params(c, SEED, mesh))[0]
        out[f"equal.{case}"] = {
            jax.tree_util.keystr(k): np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for (k, a), (_, b) in zip(one, four)}
        out[f"specs.{case}"] = {
            jax.tree_util.keystr(k): list(b.sharding.spec) for k, b in four}
        out[f"shapes.{case}"] = {
            jax.tree_util.keystr(k): [list(b.shape)] + [
                list(s.data.shape) for s in b.addressable_shards]
            for k, b in four}
    rng = np.random.default_rng(SEED)
    prompt, served = rng.integers(1, 256, 300), rng.integers(1, 256, 40)
    refs = [served_logits(cfg, make_params(cfg, SEED, m), prompt, served)
            for m in (None, mesh)]
    out["reference_equal"] = {k: refs[0][k].tobytes() == refs[1][k].tobytes()
                              for k in refs[0]}

    mp = pytest.MonkeyPatch()
    built = []
    build = R.build_engine

    def spy(*a, **kw):
        eng = build(*a, **kw)
        built.append({"tp": eng.tp,
                      "mesh": eng.mesh and dict(eng.mesh.shape),
                      "pool": {n: list(getattr(x.sharding, "spec", ()))
                               for n, x in eng.pages.cache.items()},
                      "pool_ndim": {n: x.ndim for n, x in eng.pages.cache.items()}})
        return eng

    mp.setattr(R, "build_engine", spy)
    go = tiny_runner(mp, "tiny_tp4.json", chips=4)
    res = go(seed=SEED, seconds=1.0)
    out["run"] = {"correct": res["correct"], "check": res["check"],
                  "count": res["device"]["count"]}
    altered_token(mp)
    res = go(seed=SEED, seconds=1.0)
    out["altered"] = {"correct": res["correct"], "check": res["check"]}
    mp.undo()
    out["engines"] = built
    return out


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4").strip())
    proc = subprocess.run([sys.executable, str(Path(__file__))],
                          capture_output=True, text=True, env=env, cwd=ROOT,
                          timeout=TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


@pytest.mark.parametrize("dtype", list(SPLITS))
def test_sharded_weights_are_bitwise_the_unsharded(child, dtype):
    equal = child[f"equal.{dtype}"]
    assert len(equal) == len(SPLITS[dtype]) + 3   # + ln1, ln2, final_norm
    assert all(equal.values()), equal


@pytest.mark.parametrize("dtype", list(SPLITS))
def test_each_matrix_is_split_on_model_as_the_program_lays_it_out(child, dtype):
    assert set(child[f"specs.{dtype}"]) == set(SPLITS[dtype]) | {
        "['layers']['ln1']['w']", "['layers']['ln2']['w']",
        "['final_norm']['w']"}
    for leaf, spec in child[f"specs.{dtype}"].items():
        spec = spec + [None] * (5 - len(spec))
        want = SPLITS[dtype].get(leaf)
        assert spec[:5] == [("model" if i == want else None) for i in range(5)], \
            (leaf, spec)
        whole, *shards = child[f"shapes.{dtype}"][leaf]
        share = list(whole)
        if want is not None:                        # each chip holds a quarter
            share[want] //= 4
        assert shards == [share] * 4, (leaf, whole, shards)


def test_reference_reads_sharded_weights_as_unsharded(child):
    assert child["reference_equal"] and all(child["reference_equal"].values())


def test_four_chip_cell_serves_one_tp4_engine_with_a_head_sharded_pool(child):
    engines = child["engines"]
    assert len(engines) == 2 and all(e["tp"] == 4 for e in engines)
    assert engines[0]["mesh"] == {"data": 1, "model": 4}
    for name, spec in engines[0]["pool"].items():
        spec = spec + [None] * (engines[0]["pool_ndim"][name] - len(spec))
        assert spec[2] == "model" and spec.count("model") == 1, (name, spec)
    assert child["run"]["count"] == 4


def test_four_chip_cell_is_correct(child):
    run = child["run"]
    assert run["correct"], run["check"]
    assert run["check"]["tokens_compared"]["value"] >= 20


def test_four_chip_cell_with_a_token_altered_is_not_correct(child):
    alt = child["altered"]
    assert not alt["correct"], alt["check"]
    assert alt["check"]["mean_gap"]["value"] > alt["check"]["mean_gap"]["limit"]


if __name__ == "__main__":
    print(json.dumps(_child()), flush=True)
