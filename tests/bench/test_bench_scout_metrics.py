"""The per-layer readers of the paged scout's counter
(``scout_pages_share.long`` / ``.chat``): nothing from a program without
the counter or whose scout never ran the kernel, and the mean share of
the table walked from a hand-made summary."""
import pytest

from bench.run import MetricContext, load_reader

NAMES = ("scout_pages_share.long", "scout_pages_share.chat")
CONFIG = {"deployment": {"max_len": 10240, "page_size": 128}}


def ctx(summary):
    return MetricContext(None, summary, None, CONFIG, None, None, 1)


@pytest.mark.parametrize("name", NAMES)
def test_reads_nothing_without_the_counter(name):
    """The summary of an engine before the counter, and of one whose
    scout took the XLA path (no samples), give no value and raise
    nothing."""
    read = load_reader(name)
    assert read(ctx({"page_sparsity": 0.3, "page_samples": 40})) is None
    assert read(ctx({"scout_pages": 0.0, "scout_samples": 0})) is None


@pytest.mark.parametrize("name", NAMES)
def test_share_of_the_table_on_a_hand_made_summary(name):
    """28 layers x 12 slots x 10 steps walking 47 of 80 columns each."""
    n = 28 * 12 * 10
    got = load_reader(name)(ctx({"scout_pages": 47.0 * n,
                                 "scout_samples": n}))
    assert got == pytest.approx(100.0 * 47 / 80)
