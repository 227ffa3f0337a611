"""The Tier-1.5 packed-row update writes its live rows back in place, one
``dynamic_update_slice`` per run of live rows, and gives bit for bit what a
scatter of the same packed update gives: live rows updated, frozen rows
untouched, packed moments alike.  Both the jnp update and the fused Pallas
kernel (interpret mode) are checked."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TrainConfig
from repro.core.grades import MonitorSpec
from repro.kernels.dispatch import KernelBackend
from repro.optim.optimizer import (OptState, _live_runs, apply_updates,
                                   packed_row_counts)

TRAILING = (8, 128)


def _mask(shape, live):
    m = np.zeros(int(np.prod(shape)), bool)
    m[list(live)] = True
    return m.reshape(shape)


CASES = {
    # Moonlight's (layer, expert) rows: layers 2-4 of 5 live, 8 experts each
    "moonlight": (_mask((5, 8), range(16, 40)), [(16, 40)]),
    # qwen3 ft-wave's MLP: the lowest 16 of 28 layers frozen
    "qwen3": (_mask((28,), range(16, 28)), [(16, 28)]),
    "two-runs": (_mask((40,), [*range(0, 8), *range(24, 40)]),
                 [(0, 8), (24, 40)]),
    "alternating": (_mask((8,), range(0, 8, 2)),
                    [(0, 1), (2, 3), (4, 5), (6, 7)]),
    "single-row": (_mask((8,), [5]), [(5, 6)]),
    "all-live": (np.ones((8,), bool), [(0, 8)]),
}


def _apply(p, g, m, v, count, tcfg, trainable, flags, backend, lr):
    spec = MonitorSpec(groups={"w": ((("w",),), flags.ndim)})
    params, opt = apply_updates(
        {"w": p}, {"w": g}, OptState(count=count, m={"w": m}, v={"w": v}),
        tcfg, trainable={"w": trainable}, spec=spec,
        group_frozen={"w": flags}, backend=backend, lr=lr)
    return params["w"], opt.m["w"], opt.v["w"]


def _scatter_reference(p, g, m, v, count, tcfg, row_mask, flags, backend,
                       lr):
    """The update as it was written before: the packed rows updated as an
    ordinary stacked leaf, then scattered into the stack."""
    live = np.nonzero(row_mask.reshape(-1))[0]
    rows = lambda x: x.reshape((-1,) + TRAILING)  # noqa: E731
    pn_live, mn, vn = _apply(rows(p)[live], rows(g)[live], m, v, count, tcfg,
                             True, flags.reshape(-1)[live], backend, lr)
    return rows(p).at[live].set(pn_live).reshape(p.shape), mn, vn


@pytest.mark.parametrize("kernels", ["jnp", "pallas"])
@pytest.mark.parametrize("case", list(CASES))
def test_in_place_row_writes_match_the_scatter(case, kernels):
    row_mask, runs = CASES[case]
    assert _live_runs(row_mask) == runs
    n_live = int(row_mask.sum())
    assert packed_row_counts({"w": row_mask, "b": True}) == {
        "leaves": 1, "rows": n_live, "runs": len(runs)}
    shape = row_mask.shape + TRAILING
    kp, kg, km, kv = jax.random.split(jax.random.PRNGKey(7), 4)
    p = jax.random.normal(kp, shape, jnp.float32)
    g = jax.random.normal(kg, shape, jnp.float32)
    m = 0.1 * jax.random.normal(km, (n_live,) + TRAILING, jnp.float32)
    v = jnp.abs(jax.random.normal(kv, (n_live,) + TRAILING, jnp.float32))
    # where there are several, the last live row froze since the boundary:
    # the dynamic flags mask it
    live = np.nonzero(row_mask.reshape(-1))[0]
    flags = np.zeros(row_mask.size, bool)
    flags[live[1:][-1:]] = True
    flags = jnp.asarray(flags.reshape(row_mask.shape))
    tcfg = dataclasses.replace(TrainConfig(), grad_clip=0.0)
    backend = (KernelBackend("pallas", interpret=True) if kernels == "pallas"
               else KernelBackend("jnp", False))
    lr, count = jnp.float32(1e-3), jnp.asarray(4, jnp.int32)

    got = jax.jit(lambda *a: _apply(*a, count, tcfg, row_mask, flags,
                                    backend, lr))(p, g, m, v)
    want = jax.jit(lambda *a: _scatter_reference(
        *a, count, tcfg, row_mask, flags, backend, lr))(p, g, m, v)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rows = lambda x: np.asarray(x).reshape((-1,) + TRAILING)  # noqa: E731
    frozen = ~row_mask.reshape(-1) | np.asarray(flags).reshape(-1)
    np.testing.assert_array_equal(rows(got[0])[frozen], rows(p)[frozen])
    assert (rows(got[0])[~frozen] != rows(p)[~frozen]).any(axis=(1, 2)).all()
