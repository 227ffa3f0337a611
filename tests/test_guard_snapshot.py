"""The numerics guard's boundary snapshot (``train/loop.py``): the state is
copied to host memory as the step holds it, in groups of bounded bytes, and
restored bit for bit, on the shardings it had.  Each case runs in a process
of its own, with the CPU backend forced to the device count it needs."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PRELUDE = """
import dataclasses
import jax, jax.numpy as jnp, numpy as np
import repro.train.loop as loop
from repro.train.loop import restore_snapshot, snapshot_to_host

def check_round_trip(tree):
    snap = snapshot_to_host(tree)
    assert all(type(h) is np.ndarray for h in snap.leaves)
    back = restore_snapshot(snap)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert (y.shape, y.dtype) == (x.shape, x.dtype)
        assert y.sharding == x.sharding and y.committed == x.committed
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    return snap
"""

CASES = {
    # one device: a TrainState with Tier-1.5 row-packed moments and
    # error-feedback buffers (layer 0's MLP frozen) and whole-type
    # placeholders ("layers/wq" frozen everywhere)
    "packed": (1, """
import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.core.partition import freeze_plan
from repro.optim.optimizer import align_moments, align_packed_tree
from repro.train.state import init_train_state

cfg = configs.reduced("qwen3-0.6b")
tcfg = TrainConfig(seq_len=32, global_batch=4, steps=4,
                   grad_compression="int8_ef",
                   grades=GradESConfig(enabled=True))
state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
spec = build_monitor_spec(state.params)
frozen = {k: np.array(v) for k, v in
          jax.device_get(state.grades.frozen).items()}
frozen["layers/wq"][:] = True
for name in ("layers/w_gate", "layers/w_up", "layers/w_down"):
    frozen[name][0] = True
trainable = freeze_plan(frozen, state.params, spec, cfg.n_layers,
                        tcfg.segment_max).trainable
state = dataclasses.replace(
    state, opt=align_moments(state.opt, state.params, tcfg, trainable),
    ef_error=align_packed_tree(state.ef_error, state.params, jnp.float32,
                               trainable))
p = state.params["layers"]
for tree in (state.opt.m["layers"], state.opt.v["layers"],
             state.ef_error["layers"]):
    assert tree["w_up"].shape == (1,) + p["w_up"].shape[1:]
    assert tree["wq"].shape == (1,)
leaves = jax.tree.leaves(state)
# groups of at most one leaf's size: every leaf is a group of its own
loop.D2H_GROUP_BYTES = min(x.nbytes for x in leaves)
snap = check_round_trip(state)
assert [h.shape for h in snap.leaves] == [x.shape for x in leaves]
"""),
    # four devices: a replicated leaf is held once on the host, a sharded
    # leaf once per shard (the global array), and a leaf made outside jit
    # comes back uncommitted, so the restored tree still enters a step over
    # the mesh
    "four_devices": (4, """
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.sharding import make_mesh

mesh = make_mesh((4,), ("data",))
tree = {"rep": jax.device_put(np.arange(32, dtype=np.float32).reshape(4, 8),
                              NamedSharding(mesh, P())),
        "shd": jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8),
                              NamedSharding(mesh, P("data"))),
        "unc": jnp.ones((1,), jnp.bfloat16)}
snap = check_round_trip(tree)
assert [h.shape for h in snap.leaves] == [(4, 8), (8, 8), (1,)]
assert sum(h.nbytes for h in snap.leaves) == 386
back = restore_snapshot(snap)
total = jax.jit(lambda t: t["rep"].sum() + t["shd"].sum() + t["unc"].sum())
assert float(total(back)) == float(total(tree)) == 496 + 2016 + 1
"""),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_snapshot_round_trip(case):
    devices, body = CASES[case]
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, "-c", PRELUDE + body + "print('OK')"],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0 and p.stdout.strip().endswith("OK"), \
        p.stdout + p.stderr
