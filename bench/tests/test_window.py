"""The measured window at a tiny size on the CPU: it spans whole repartition
periods, at least two; nothing is built inside it on a sound run; and a
re-jit planted inside it is counted even when the persistent compilation
cache holds the program, so that no backend compile runs."""
import time

import jax
import pytest
from jax._src import compilation_cache

import data
import program
import weights
from helpers import tiny_cell

SEED = 2**31 + 777
CACHE_HIT = "/jax/compilation_cache/cache_hits"


@pytest.fixture
def warm_cache(tmp_path):
    """JAX's persistent compilation cache in ``tmp_path``, caching every
    program, for the test only."""
    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    old = {n: getattr(jax.config, n) for n in names}
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.clear_caches()      # so that every program of the test reaches it
    yield tmp_path
    for n, v in old.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def run_tiny(cell, seconds=0.0):
    gen = data.NoisyPermutation(cell.vocab, cell.seq_len, cell.rows, SEED,
                                float(cell.traffic["data"]["noise"]))
    return program.run(cell, SEED, seconds, gen,
                       lambda: weights.make(cell.config, SEED))


def test_window_spans_at_least_two_periods():
    cell = tiny_cell()
    got = run_tiny(cell)
    win = got["window"]
    assert (win.s1 - win.s0) == program.MIN_PERIODS * cell.steps_per_period
    assert win.s0 % cell.steps_per_period == 0
    assert got["compiles_in_window"] == 0


def test_rejit_in_the_window_is_counted_with_a_warm_cache(warm_cache,
                                                          monkeypatch):
    cell = tiny_cell()
    assert run_tiny(cell)["compiles_in_window"] == 0      # fills the cache
    hits = []

    def on_event(event, **_kw):
        if event == CACHE_HIT:
            hits.append(time.time())
    jax.monitoring.register_event_listener(on_event)

    # plant: at the first drain inside the window, drop every in-memory
    # cache, so the trainer's next dispatch re-traces and re-lowers its step
    # and loads the executable from the persistent cache
    orig = program.Window.__call__

    def planted(self, step, ema):
        orig(self, step, ema)
        if self.t0 is not None and not self.closed \
                and step == self.s0 + cell.traffic["trainer"]["sync_interval"]:
            jax.clear_caches()
    monkeypatch.setattr(program.Window, "__call__", planted)
    try:
        got = run_tiny(cell)
    finally:
        jax.monitoring.unregister_event_listener(on_event)
    win = got["window"]
    assert any(win.wall0 <= t <= win.wall1 for t in hits), \
        "the planted re-jit did not load from the persistent cache"
    assert got["compiles_in_window"] >= 1
