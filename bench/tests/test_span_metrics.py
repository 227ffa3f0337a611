"""The host-controller readers (``bench/spans.py``) on a small recorded
trace with the program's spans (``data/spans.txt``)."""
import os

import jax
import pytest

import run
import spans
import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("guard_snapshot_idle_share.train", "prefetch_wait_share.train",
           "idle_unattributed_share.train")


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "spans.txt")) as f:
        text = "".join(l for l in f if not l.startswith("#"))
    return trace_reduce.Trace(jax.profiler.ProfileData.from_text_proto(text))


def read(name, trace):
    return run.metric_reader(name)({"trace": trace})


def test_idle_gaps_of_the_recorded_trace(trace):
    # window [1000, 21000]: 5000 + 3000 + 3000 + 500 ns idle of 20000
    assert spans.idle(trace) == [[(4000, 9000), (12000, 15000),
                                  (16000, 19000), (20500, 21000)]]
    assert run.metric_reader("device_idle_share.train")(
        {"trace": trace}) == pytest.approx(57.5)


def test_guard_snapshot_idle_share(trace):
    # [4500, 8000] of the first gap (the child adds nothing), and the last
    # snapshot cut by the window's end to [20500, 21000]: 4000 of 20000 ns
    assert read("guard_snapshot_idle_share.train", trace) == \
        pytest.approx(20.0)


def test_prefetch_wait_share(trace):
    # [8200, 8600] in an idle gap and [19500, 20000] while an op runs
    assert read("prefetch_wait_share.train", trace) == pytest.approx(4.5)


def test_idle_unattributed_share(trace):
    # first gap: [8000, 8200] and [8600, 9000] are under no span (the drain
    # is cut to the window but still covers [4000, 4500]); the second gap is
    # under the prefetch thread's placement; the third under a runtime
    # event only; the last under the cut snapshot: 600 + 3000 of 20000 ns
    assert read("idle_unattributed_share.train", trace) == \
        pytest.approx(18.0)


def test_span_names_drop_their_arguments():
    assert spans.span_name("/repro/train/drain#step=8#") == \
        "/repro/train/drain"
    assert spans.span_name("/repro/data/place") == "/repro/data/place"


def test_interval_helpers():
    a, b = [(0, 10), (20, 30)], [(5, 7), (8, 22), (29, 40)]
    assert spans.overlap(a, b) == 7
    assert spans.uncovered(a, b) == 13
    assert spans.uncovered(a, []) == 20
    assert spans.merged([(3, 4), (0, 2), (1, 3)]) == [[0, 4]]


def test_a_program_without_spans_reads_nothing(trace, monkeypatch):
    """The parent of this metric's program has no ``repro.tracing``: its
    traced run leaves the three metrics out of the line."""
    monkeypatch.setattr(spans, "instrumented", lambda: False)
    assert [read(name, trace) for name in READERS] == [None] * 3
