"""End-to-end training driver: ~100M-parameter LM, packed-file data pipeline,
async checkpointing, GradES early stopping, auto-resume after interruption.

    PYTHONPATH=src python examples/train_100m.py --preset small   # CPU-friendly
    PYTHONPATH=src python examples/train_100m.py                  # full ~100M
"""
import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.config import GradESConfig, ModelConfig, TrainConfig
from repro.data.pipeline import PackedFileDataset, SyntheticTask
from repro.train.loop import Trainer

PRESETS = {
    # ~100M params: 12L x 768 with a 32k vocab
    "full": dict(model=ModelConfig(name="lm-100m", n_layers=12, d_model=768,
                                   n_heads=12, n_kv_heads=4, d_ff=3072,
                                   vocab=32768, head_dim=64),
                 seq=512, batch=8, steps=300),
    # CPU demo: same family, minutes not hours
    "small": dict(model=ModelConfig(name="lm-8m", n_layers=4, d_model=256,
                                    n_heads=8, n_kv_heads=4, d_ff=1024,
                                    vocab=4096, head_dim=32),
                  seq=128, batch=8, steps=300),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=list(PRESETS), default="small")
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--data", default="", help="pre-tokenized .npy (else generated)")
    ap.add_argument("--ckpt", default="", help="checkpoint dir (default: temp)")
    args = ap.parse_args()
    p = PRESETS[args.preset]
    cfg: ModelConfig = p["model"]
    steps = args.steps or p["steps"]
    print(f"model={cfg.name} params={cfg.param_count()/1e6:.1f}M steps={steps}")

    # --- data: packed token file (generated from the synthetic task if absent)
    data_path = args.data
    if not data_path:
        data_path = os.path.join(tempfile.gettempdir(), f"{cfg.name}_tokens.npy")
        if not os.path.exists(data_path):
            task = SyntheticTask(cfg.vocab, p["seq"], noise=0.05, seed=0)
            rng = np.random.default_rng(0)
            docs = task.sample(rng, 2048)
            packed = np.concatenate([docs["tokens"], docs["labels"][:, -1:]], 1)
            PackedFileDataset.write(data_path, packed)
            print(f"wrote {data_path} {packed.shape}")
    ds = PackedFileDataset(data_path, p["seq"])

    ckpt = args.ckpt or os.path.join(tempfile.gettempdir(), f"{cfg.name}_ckpt")
    tcfg = TrainConfig(
        seq_len=p["seq"], global_batch=p["batch"], steps=steps, lr=3e-3,
        remat="none", checkpoint_dir=ckpt, checkpoint_every=max(steps // 5, 10),
        grades=GradESConfig(enabled=True, tau=2e-3, alpha=0.4, normalize=True,
                            patience=2),
    )
    trainer = Trainer(cfg, tcfg, log_every=10,
                      log_path=os.path.join(ckpt, "metrics.jsonl"))
    # Callable form: the trainer calls it with the resumed step index, so a
    # restart continues the shuffled stream instead of replaying batch 0.
    res = trainer.train(
        batches=lambda start: ds.batches(p["batch"], start_step=start))
    print(f"\nstop={res.stop_reason} steps_run={res.steps_run} "
          f"wall={res.wall_time:.1f}s recompiles={res.recompiles}")
    if res.history:
        h0, h1 = res.history[0], res.history[-1]
        print(f"loss {h0['loss']:.3f} -> {h1['loss']:.3f}; "
              f"frozen_frac {h1['frozen_frac']:.2f}")
    print(f"checkpoints in {ckpt}: re-run this command to auto-resume.")


if __name__ == "__main__":
    main()
