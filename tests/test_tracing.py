"""Host spans of the trainer and the prefetcher (``repro.tracing``): each span
reaches both ``jax.monitoring`` and the profiler's trace, nests under the
span that encloses it on its thread, and shares the trace's clock up to one
fixed offset; the compiled step carries named scopes for its phases."""
import glob
import threading

import jax
import pytest

import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.data.pipeline import Prefetcher, make_batches, stack_batches
from repro.robustness.faults import FaultPlan, FaultyBatchSource
from repro.tracing import mark, span
from repro.train.loop import Trainer
from repro.train.state import init_train_state
from repro.train.step import make_multi_step

CFG = configs.reduced("qwen3-0.6b")
T = "/repro/train/"


def _tcfg(**kw):
    # 3 blocks of 2 steps; "layers/wq" freezes at once, so the boundary at
    # step 4 (repartition_interval 4) changes the plan and re-jits
    base = dict(seq_len=32, global_batch=4, steps=6, lr=3e-3, sync_interval=2,
                numerics_guard=True,
                grades=GradESConfig(enabled=True, alpha=0.0, tau=1e-9,
                                    tau_overrides={"layers/wq": 1e9}))
    base.update(kw)
    return TrainConfig(**base)


class Recorder:
    """A ``jax.monitoring`` time-span listener keeping the program's spans."""

    def __init__(self):
        self.spans = []

    def __call__(self, event, start, end, **attrs):
        if event.startswith("/repro/"):
            self.spans.append((event, start, end, attrs))

    def __enter__(self):
        jax.monitoring.register_event_time_span_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_time_span_listener(self)

    def named(self, name):
        return sorted((s for s in self.spans if s[0] == name),
                      key=lambda s: s[1])


def _trace_events(directory):
    """(name, start_ns, end_ns) of every host event in the profiler trace."""
    (path,) = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns) for p in profile.planes
            if p.name == "/host:CPU" for line in p.lines for e in line.events]


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One guarded run, recorded by a listener and by JAX's profiler."""
    directory = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with Recorder() as rec:
        jax.profiler.start_trace(directory, profiler_options=opts)
        try:
            res = Trainer(CFG, _tcfg(), repartition_interval=4,
                          log_every=1).train()
        finally:
            jax.profiler.stop_trace()
    return res, rec, _trace_events(directory)


def test_one_dispatch_and_drain_per_block(traced_run):
    res, rec, _ = traced_run
    assert res.steps_run == 6 and res.recompiles == 1
    for name in ("dispatch", "drain", "prefetch_wait"):
        assert [s[3]["step"] for s in rec.named(T + name)] == [0, 2, 4], name
    # every block was built and placed on the prefetch thread
    assert len(rec.named("/repro/data/build")) == 3
    assert len(rec.named("/repro/data/place")) == 3


def test_guard_snapshot_at_start_and_each_boundary(traced_run):
    _, rec, _ = traced_run
    snaps = rec.named(T + "guard_snapshot")
    assert [(s[3]["step"], s[3]["parent"]) for s in snaps] == \
        [(0, ""), (2, T + "boundary")]
    for snap in snaps:
        kids = [s for s in rec.spans if s[3].get("parent") == snap[0]
                and snap[1] <= s[1] and s[2] <= snap[2]]
        # the state as the step holds it, copied to pinned host memory:
        # no checkpoint-layout expansion on the way
        assert [(k[0], k[3]["bytes"] > 0) for k in kids] == \
            [(T + "state_to_host", True)]


def test_boundary_parents_its_work(traced_run):
    _, rec, _ = traced_run
    (boundary,) = rec.named(T + "boundary")
    assert boundary[3] == {"parent": "", "step": 2}
    for name in ("freeze_masks", "repartition", "guard_snapshot"):
        kids = [s for s in rec.named(T + name) if s[3]["parent"] == T +
                "boundary"]
        assert len(kids) == 1 and kids[0][3]["step"] == 2, name
    # the boundary settles its own block: one of the three drains
    assert [s[3]["parent"] for s in rec.named(T + "drain")] == \
        ["", T + "boundary", ""]


def test_children_lie_inside_their_parents(traced_run):
    _, rec, _ = traced_run
    children = [s for s in rec.spans if s[3]["parent"]]
    assert children
    for name, start, end, attrs in children:
        assert any(p[1] <= start and end <= p[2]
                   for p in rec.named(attrs["parent"])), name
    # no span wraps the whole run: the outermost are per-block phases
    outer = {s[0] for s in rec.spans if not s[3]["parent"]}
    assert outer == {T + n for n in ("guard_snapshot", "prefetch_wait",
                                     "dispatch", "drain", "boundary")} \
        | {"/repro/data/build"}


def test_spans_share_the_profiler_clock(traced_run):
    """Each listener span has a trace event of its name and length; one
    offset maps the listener's clock onto the trace's for all of them."""
    _, rec, events = traced_run
    ours = [s for s in rec.spans if s[0].startswith(T)]
    assert len(ours) >= 15
    offsets = []
    for name in {s[0] for s in ours}:
        spans = rec.named(name)
        found = sorted((e for e in events if e[0].split("#")[0] == name),
                       key=lambda e: e[1])
        assert len(found) == len(spans), name
        for (_, t0, t1, _), (_, s_ns, e_ns) in zip(spans, found):
            assert abs((e_ns - s_ns) * 1e-9 - (t1 - t0)) < 1e-3, name
            offsets.append(s_ns * 1e-9 - t0)
    assert max(offsets) - min(offsets) < 1e-3


def test_rollback_is_one_span():
    tcfg = _tcfg(grades=GradESConfig(enabled=False),
                 fault_plan=FaultPlan.parse(["nan_grad@3"]))
    with Recorder() as rec:
        res = Trainer(CFG, tcfg, log_every=1).train()
    assert res.rollbacks == 1
    (rollback,) = rec.named(T + "rollback")
    assert rollback[3] == {"parent": "", "step": 2}
    # the snapshot it restored: the start's, the one boundary before the trip
    assert len(rec.named(T + "guard_snapshot")) == 1


@pytest.mark.parametrize("depth", [0, 2])
def test_prefetcher_spans_count_its_retries(depth):
    """Two injected read errors: two retries, each a span, on the thread
    that builds the blocks (the prefetch thread, or the caller's at depth
    0); every block is one build holding one placement."""
    tcfg = _tcfg()
    source = FaultyBatchSource(make_batches(CFG, tcfg, steps=8),
                               FaultPlan.parse(["io_error@3:2"]))
    with Recorder() as rec:
        blocks = list(Prefetcher(source, [4, 4], depth=depth, retries=3,
                                 retry_backoff=0.0))
    assert len(blocks) == 2
    retries = rec.named("/repro/data/read_retry")
    assert [s[3] for s in retries] == [
        {"parent": "/repro/data/build", "attempt": 1},
        {"parent": "/repro/data/build", "attempt": 2}]
    assert [s[3] for s in rec.named("/repro/data/build")] == \
        [{"parent": "", "size": 4}] * 2
    assert len(rec.named("/repro/data/place")) == 2


def test_span_nesting_is_per_thread_and_survives_raises():
    def other():
        with span("/repro/test/other"):
            pass

    with Recorder() as rec:
        with span("/repro/test/outer", step=1):
            worker = threading.Thread(target=other)
            worker.start()
            worker.join(timeout=10)
            with pytest.raises(KeyError):
                with span("/repro/test/inner"):
                    raise KeyError("x")
        with span("/repro/test/after"):
            pass   # the raise left no name behind on the stack
    assert not worker.is_alive()
    got = {s[0]: s[3] for s in rec.spans}
    assert got == {"/repro/test/outer": {"parent": "", "step": 1},
                   "/repro/test/other": {"parent": ""},
                   "/repro/test/inner": {"parent": "/repro/test/outer"},
                   "/repro/test/after": {"parent": ""}}


def test_named_scopes_in_the_lowered_step():
    tcfg = _tcfg(grad_compression="int8_ef")
    state = init_train_state(jax.random.PRNGKey(0), CFG, tcfg)
    spec = build_monitor_spec(state.params)
    block = stack_batches(list(make_batches(CFG, tcfg, steps=2)))
    hlo = jax.jit(make_multi_step(CFG, tcfg, spec)).lower(
        state, block).as_text(debug_info=True)
    for scope in ("fwd_bwd", "ef_compress", "grades_monitor", "optimizer"):
        assert f"/{scope}/" in hlo, scope


def test_a_mark_keeps_its_arguments_in_the_trace_events_name(tmp_path):
    """The profiler decodes a span's keyword arguments into event stats; a
    mark's stay in the event's name, and listeners get them as keywords."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with Recorder() as rec, span(T + "drain", step=8):
            mark(T + "expert_load", step=8, assigned=12, assigned_live=5)
    finally:
        jax.profiler.stop_trace()
    names = [e[0] for e in _trace_events(str(tmp_path))]
    assert T + "expert_load#step=8,assigned=12,assigned_live=5" in names
    assert T + "drain" in names
    (got,) = rec.named(T + "expert_load")
    assert got[1] == got[2] and got[3] == {
        "parent": T + "drain", "step": 8, "assigned": 12, "assigned_live": 5}
