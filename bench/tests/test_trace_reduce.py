"""The trace reduction on a small recorded trace (``data/trace.txt``)."""
import os

import jax
import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "trace.txt")) as f:
        text = "".join(l for l in f if not l.startswith("#"))
    return trace_reduce.Trace(jax.profiler.ProfileData.from_text_proto(text))


def test_window_and_busy(trace):
    # window [1000, 11000] ns; ops cover [1000, 7000] and [10000, 11000]
    assert trace.window_s == pytest.approx(10e-6)
    assert trace.busy_s() == pytest.approx(7e-6)


def test_kernels_clipped_to_window(trace):
    calls, seconds = trace.kernel("flash_fwd")
    # [500, 3000] clipped to [1000, 3000]; [10000, 14000] to [10000, 11000]
    assert calls == 2 and seconds == pytest.approx(3e-6)
    assert trace.kernel("masked_adamw") == (1, pytest.approx(2e-6))
    assert trace.kernel("grades_norm") == (0, 0.0)


def test_breakdown(trace):
    b = trace.breakdown()
    names = [n for n, _ in b["device_ops"]]
    assert not any(n.startswith("%while") for n in names)
    assert names[0].startswith("%flash_fwd.35 = (bf16[4,8,4096,128])")
    assert b["device_ops"][0][1] == pytest.approx(3e-6)
    # one idle gap, [7000, 10000], inside the device_get span
    assert b["idle_gaps"] == [["PjitFunction(multi_step)",
                               pytest.approx(3e-6)]]


def test_interval_helpers():
    assert trace_reduce.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace_reduce.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4),
                                                          (5, 6)]
    assert trace_reduce.op_kind("%flash_dq.22 = f32[4] custom-call()") \
        == "flash_dq"


def test_trace_without_window_is_refused():
    text = 'planes { id: 1 name: "/host:CPU" }'
    with pytest.raises(ValueError, match="bench_window"):
        trace_reduce.Trace(jax.profiler.ProfileData.from_text_proto(text))


def test_metric_readers_on_the_recorded_trace(trace):
    """Each per-layer reader of BENCHMARK.json reads the recorded trace;
    none reads a share above 100% or below 0."""
    import json
    import run
    from helpers import tiny_cell
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ctx = {"cell": tiny_cell(), "steps": 32, "window_s": trace.window_s,
           "compiles": 0,
           "peaks": run.device_peaks("TPU v5 lite"), "trace": trace}
    got = {m["name"]: run.metric_reader(m["name"])(ctx)
           for m in bench["per_layer"]}
    assert got["compiles_in_window.train"] == 0
    assert got["device_idle_share.train"] == pytest.approx(30.0)
    # the step's required FLOPs over the device's busy time, not the window
    import flops
    assert got["step_mfu.train"] == pytest.approx(
        100 * flops.step_flops(ctx["cell"]) * 32 / 7e-6 / 197e12)
    # no grades_norm in the trace, but masked_adamw ran: a share, not None
    for name, value in got.items():
        if name != "compiles_in_window.train":
            assert value is not None and value >= 0, (name, value)
    assert got["flash_attention_roofline"] <= 100
