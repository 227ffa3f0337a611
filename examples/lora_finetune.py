"""LoRA + GradES (paper §3.2): adapters train, base is frozen, GradES monitors
||∇A||₁+||∇B||₁ per (layer, matrix) and freezes pairs jointly.

    PYTHONPATH=src python examples/lora_finetune.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.launch.cache import enable_compile_cache
import repro.configs as configs
from repro.config import GradESConfig, LoRAConfig, TrainConfig
from repro.train.loop import Trainer


def main():
    enable_compile_cache()
    cfg = configs.reduced("yi-9b")
    tcfg = TrainConfig(
        seq_len=32, global_batch=8, steps=250, lr=1e-2,
        lora=LoRAConfig(rank=8, targets=("wq", "wk", "wv", "wo",
                                         "w_gate", "w_up", "w_down")),
        grades=GradESConfig(enabled=True, tau=1e-3, alpha=0.3, normalize=True,
                            patience=2),
    )
    res = Trainer(cfg, tcfg, log_every=25).train()
    print(f"stop={res.stop_reason} steps={res.steps_run}")
    for h in res.history:
        print(f"step {h['step']:>4}  loss {h['loss']:.3f}  "
              f"frozen {h['frozen_frac']:.2f}")
    frozen = jax.device_get(res.state.grades.frozen)
    print("\nfrozen (A,B) pairs per layer:")
    for k, v in frozen.items():
        print(f"  {k:24s} {v.tolist()}")


if __name__ == "__main__":
    main()
