"""Multi-device tests (8 placeholder CPU devices via a SUBPROCESS so the main
pytest process keeps its single-device view).

The shard-mapped fused-kernel equivalence tests (both monitor modes, frozen
rows bit-identical, compile-count regression) are marked ``slow`` and run in
CI's non-blocking extended lane; single-device wrapper plumbing is covered in
tier-1 by ``tests/test_dispatch.py``."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, timeout=900):
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert p.returncode == 0, p.stdout + p.stderr
    return p.stdout


def test_sharded_train_step_matches_single_device():
    out = run_py("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.data.pipeline import make_batches
from repro.distributed.sharding import use_mesh, make_mesh, DEFAULT_RULES
from repro.models import model
from repro.train.state import init_train_state
from repro.train.step import make_train_step

cfg = configs.reduced("yi-9b")
tcfg = TrainConfig(seq_len=32, global_batch=8, steps=10, lr=1e-3,
                   grades=GradESConfig(enabled=True, alpha=0.5))
batches = list(make_batches(cfg, tcfg, steps=3))
state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
spec = build_monitor_spec(state.params)
step = make_train_step(cfg, tcfg, spec)

# single device reference
s1 = state
for b in batches:
    s1, m1 = jax.jit(step)(s1, b)

# sharded on a (2 data, 4 model) mesh
mesh = make_mesh((2, 4), ("data", "model"))
with use_mesh(mesh, DEFAULT_RULES):
    s2 = state
    fn = jax.jit(step)
    for b in batches:
        b = jax.device_put(b, NamedSharding(mesh, P("data")))
        s2, m2 = fn(s2, b)

for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                jax.tree.leaves(jax.device_get(s2.params))):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-3, rtol=5e-2)
print("LOSS", float(m1["loss"]), float(m2["loss"]))
""")
    l1, l2 = [float(x) for x in out.split("LOSS")[1].split()]
    assert abs(l1 - l2) < 5e-2


def test_dryrun_cell_tiny_mesh():
    """The dry-run machinery end-to-end on a small mesh (reduced arch)."""
    run_py("""
import jax, jax.numpy as jnp
import repro.configs as configs
from repro.config import SHAPES
import dataclasses
from repro.launch import roofline as rf
from repro.launch.specs import dryrun_train_cfg, train_cell_specs
from repro.core.grades import build_monitor_spec
from repro.distributed.sharding import use_mesh, make_mesh, DEFAULT_RULES
from repro.train.step import make_train_step

cfg = dataclasses.replace(configs.reduced("deepseek-coder-33b"))
mesh = make_mesh((2, 4), ("data", "model"))
cell = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
tcfg = dataclasses.replace(dryrun_train_cfg(cfg, cell), seq_len=64, global_batch=8)
with use_mesh(mesh, DEFAULT_RULES):
    state_sds, batch_sds, state_sh, batch_sh = train_cell_specs(cfg, tcfg, mesh)
    spec = build_monitor_spec(state_sds.params)
    fn = jax.jit(make_train_step(cfg, tcfg, spec),
                 in_shardings=(state_sh, batch_sh),
                 out_shardings=(state_sh, None), donate_argnums=0)
    compiled = fn.lower(state_sds, batch_sds).compile()
    out = rf.analyze_hlo(compiled.as_text())
    assert out["flops"] > 0 and out["coll_bytes"] > 0, out
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes > 0
print("OK")
""")


@pytest.mark.slow
@pytest.mark.parametrize("monitor", ["delta", "norm_delta"])
def test_sharded_fused_dispatch_matches_jnp(monitor):
    """Shard-mapped fused pipeline vs the jnp reference on a (2 data, 4 model)
    mesh: freeze decisions identical, Eq.-1 norms equal to the single-device
    fused path, frozen rows bit-identical through the sharded kernels."""
    out = run_py(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec, grades_update, init_grades_state
from repro.distributed.sharding import make_mesh
from repro.kernels import dispatch
from repro.optim.optimizer import apply_updates, init_opt_state

mesh = make_mesh((2, 4), ("data", "model"))
L = 3
ks = jax.random.split(jax.random.PRNGKey(0), 4)
params = {{
    "embed": jax.random.normal(ks[0], (16, 8)),            # unmonitored
    "layers": {{
        "wq": jax.random.normal(ks[1], (L, 8, 16)),
        "w_up": jax.random.normal(ks[2], (L, 8, 16)),
        "w_gate": jax.random.normal(ks[3], (L, 4, 8, 16)),  # gran-2 experts
    }},
}}
# hand-written leaf specs: trailing dims on both mesh axes for wq, the expert
# (granularity) axis itself on "model" for w_gate -> exercises flag slicing
param_specs = {{
    ("layers", "wq"): P(None, "data", "model"),
    ("layers", "w_up"): P(None, None, "model"),
    ("layers", "w_gate"): P(None, "model", "data", None),
}}
spec = build_monitor_spec(params)
gcfg = GradESConfig(enabled=True, tau=1e-1, alpha=0.0, patience=1,
                    monitor="{monitor}", normalize=True)
tcfg = TrainConfig(optimizer="adamw", lr=1e-2, steps=10, grades=gcfg,
                   weight_decay=0.01, grad_clip=1.0)
sh = dispatch.KernelBackend("pallas", True, mesh, forced=True)
one = dispatch.KernelBackend("pallas", True)
ref = dispatch.resolve_backend("jnp")

def grad_seq(i):
    scale = 1.0 if i < 2 else 1e-3
    return jax.tree.map(lambda p: jax.random.normal(
        jax.random.PRNGKey(i), p.shape) * scale, params)

stA, stB, stC = (init_grades_state(params, spec, gcfg) for _ in range(3))
optA, optB = (init_opt_state(params, tcfg) for _ in range(2))
pA = pB = params
froze = False
for i in range(4):
    g = grad_seq(i)
    stA, frA = grades_update(stA, g, spec, gcfg, 10, backend=sh,
                             param_specs=param_specs)
    stB, frB = grades_update(stB, g, spec, gcfg, 10, backend=ref)
    stC, _ = grades_update(stC, g, spec, gcfg, 10, backend=one)
    for n in frA:
        assert (np.asarray(frA[n]) == np.asarray(frB[n])).all(), n
        np.testing.assert_allclose(np.asarray(stA.last_norm[n]),
                                   np.asarray(stB.last_norm[n]),
                                   rtol=2e-3, err_msg=n)
        # Eq.-1 norms equal to the single-device fused path
        np.testing.assert_allclose(np.asarray(stA.last_norm[n]),
                                   np.asarray(stC.last_norm[n]),
                                   rtol=2e-3, err_msg=n)
    prev_pA = pA
    pA, optA = apply_updates(pA, g, optA, tcfg, spec=spec, group_frozen=frA,
                             backend=sh, param_specs=param_specs)
    pB, optB = apply_updates(pB, g, optB, tcfg, spec=spec, group_frozen=frB,
                             backend=ref)
    for name in ("wq", "w_up", "w_gate"):
        fz = np.asarray(frA[f"layers/{{name}}"])
        if fz.any():
            froze = True
            before = np.asarray(prev_pA["layers"][name])[fz]
            after = np.asarray(pA["layers"][name])[fz]
            assert (before == after).all(), name  # bit-identical frozen rows
assert froze, "test never exercised a frozen row"
for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-5, atol=2e-6)
for a, b in zip(jax.tree.leaves(optA.m), jax.tree.leaves(optB.m)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-5, atol=2e-6)
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_sharded_fused_step_compiles_once_under_schedule():
    """The shard-mapped fused train step on a (2, 4) mesh compiles exactly
    once across a 10-step cosine-schedule run (lr/count stay dynamic through
    the shard_map wrappers)."""
    out = run_py("""
import jax, numpy as np
import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.data.pipeline import make_batches
from repro.distributed.sharding import use_mesh, make_mesh, DEFAULT_RULES
from repro.kernels.dispatch import resolve_backend
from repro.launch.specs import train_cell_specs
from repro.train.state import init_train_state
from repro.train.step import make_train_step

cfg = configs.reduced("yi-9b")
tcfg = TrainConfig(seq_len=32, global_batch=8, steps=10, lr=1e-3,
                   schedule="cosine", kernels="pallas",
                   grades=GradESConfig(enabled=True, alpha=0.2, tau=1e-2,
                                       patience=1))
state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
spec = build_monitor_spec(state.params)
mesh = make_mesh((2, 4), ("data", "model"))
with use_mesh(mesh, DEFAULT_RULES):
    _, _, state_sh, batch_sh = train_cell_specs(cfg, tcfg, mesh)
    backend = resolve_backend(tcfg.kernels)
    assert backend.use_pallas and backend.mesh is not None
    state = jax.device_put(state, state_sh)
    step = jax.jit(make_train_step(cfg, tcfg, spec, backend=backend),
                   in_shardings=(state_sh, batch_sh),
                   out_shardings=(state_sh, None))
    lrs = []
    for b in make_batches(cfg, tcfg, steps=10):
        state, metrics = step(state, jax.device_put(b, batch_sh))
        lrs.append(float(metrics["lr"]))
assert step._cache_size() == 1, step._cache_size()
assert len(set(lrs)) > 1, "schedule did not vary lr"
print("OK")
""")
    assert "OK" in out


@pytest.mark.slow
def test_sharded_fused_train_step_matches_single_device():
    """Full train step, fused kernels on the (2, 4) mesh vs the single-device
    fused path: params and Eq.-1 monitor norms agree."""
    out = run_py("""
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.data.pipeline import make_batches
from repro.distributed.sharding import use_mesh, make_mesh, DEFAULT_RULES
from repro.kernels.dispatch import resolve_backend
from repro.train.state import init_train_state
from repro.train.step import make_train_step

cfg = configs.reduced("yi-9b")
tcfg = TrainConfig(seq_len=32, global_batch=8, steps=10, lr=1e-3,
                   kernels="pallas",
                   grades=GradESConfig(enabled=True, alpha=0.2, tau=1e-2,
                                       patience=1))
batches = list(make_batches(cfg, tcfg, steps=3))
state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
spec = build_monitor_spec(state.params)

s1 = state
step1 = jax.jit(make_train_step(cfg, tcfg, spec))
for b in batches:
    s1, m1 = step1(s1, b)

mesh = make_mesh((2, 4), ("data", "model"))
with use_mesh(mesh, DEFAULT_RULES):
    backend = resolve_backend(tcfg.kernels)
    step2 = jax.jit(make_train_step(cfg, tcfg, spec, backend=backend))
    s2 = state
    for b in batches:
        b = jax.device_put(b, NamedSharding(mesh, P("data")))
        s2, m2 = step2(s2, b)

for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                jax.tree.leaves(jax.device_get(s2.params))):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=5e-3, rtol=5e-2)
for n in s1.grades.last_norm:
    np.testing.assert_allclose(np.asarray(s1.grades.last_norm[n]),
                               np.asarray(s2.grades.last_norm[n]),
                               rtol=2e-3, err_msg=n)
print("LOSS", float(m1["loss"]), float(m2["loss"]))
""")
    l1, l2 = [float(x) for x in out.split("LOSS")[1].split()]
    assert abs(l1 - l2) < 5e-2


def test_elastic_restore_different_mesh():
    """Checkpoint written on one mesh restores onto another (elastic restart)."""
    run_py("""
import jax, jax.numpy as jnp, numpy as np, tempfile, shutil
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.configs as configs
from repro.config import TrainConfig
from repro.checkpoint.manager import CheckpointManager
from repro.distributed.sharding import make_mesh
from repro.train.state import init_train_state

cfg = configs.reduced("yi-9b")
tcfg = TrainConfig(seq_len=16, global_batch=4, steps=5)
state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
d = tempfile.mkdtemp()
try:
    ck = CheckpointManager(d)
    ck.save(1, state, blocking=True)
    # restore with every leaf replicated on a 8-device mesh ("new cluster shape")
    mesh = make_mesh((8,), ("data",))
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
    restored = ck.restore(1, state, shardings=sh)
    for a, b in zip(jax.tree.leaves(jax.device_get(state)),
                    jax.tree.leaves(jax.device_get(restored))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
finally:
    shutil.rmtree(d)
print("OK")
""")
