"""Quickstart: fine-tune a small LM with GradES and watch matrices freeze.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.launch.cache import enable_compile_cache
import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.train.loop import Trainer


def main():
    enable_compile_cache()
    cfg = configs.reduced("qwen3-0.6b")
    tcfg = TrainConfig(
        seq_len=32, global_batch=8, steps=300, lr=3e-3,
        grades=GradESConfig(enabled=True, tau=4e-3, alpha=0.3,
                            normalize=True, patience=2),
    )
    trainer = Trainer(cfg, tcfg, repartition_interval=10, log_every=25)
    res = trainer.train()
    print(f"\nstop={res.stop_reason}  steps={res.steps_run}  "
          f"tier1_recompiles={res.recompiles}")
    print(f"{'step':>6} {'loss':>8} {'frozen':>8} {'ms/step':>8}")
    for h in res.history:
        print(f"{h['step']:>6} {h['loss']:>8.3f} {h['frozen_frac']:>8.2f} "
              f"{h['dt']*1e3:>8.1f}")
    frozen = jax.device_get(res.state.grades.frozen)
    print("\nper-matrix freeze state (True = stopped training):")
    for k, v in frozen.items():
        print(f"  {k:24s} {v.tolist()}")


if __name__ == "__main__":
    main()
