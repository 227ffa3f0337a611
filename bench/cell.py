"""A benchmark cell, read from data files found by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix.  The
files behind them:

* ``bench/configs/<config>.json`` -- the model as it is run, under the keys
  of its published ``config.json``, with ``source``, ``reduced``,
  ``assumed``, ``departures``, ``deployment`` and ``reference`` (the plain
  reference under ``bench/references/``).
* ``bench/traffic/<traffic>.json`` -- the fine-tune job: row length, the
  trainer's and optimizer's settings, and which matrices are frozen at
  set-up.
* ``bench/cells/<cell>.json`` -- what belongs to the pair alone: the rows per
  step that fit one chip, and the comparison limits of ``correct``.

Adding a cell, a configuration or a mix adds files; no code changes.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    pair: Dict[str, Any]

    # ------------------------------------------------------------- model
    @property
    def n_layers(self) -> int:
        return int(self.config["num_hidden_layers"])

    @property
    def vocab(self) -> int:
        return int(self.config["vocab_size"])

    @property
    def seq_len(self) -> int:
        return int(self.traffic["seq_len"])

    @property
    def rows(self) -> int:
        """Rows per step over all chips of the cell."""
        return int(self.pair["rows_per_chip"]) * self.chips

    @property
    def steps_per_period(self) -> int:
        """Steps between two repartition boundaries of the trainer."""
        t = self.traffic["trainer"]
        k = int(t["sync_interval"])
        return -(-int(t["repartition_interval"]) // k) * k

    def frozen_rows(self) -> Dict[str, List[bool]]:
        """Matrix type -> per-layer frozen flags written at set-up.

        ``all_layers`` types are frozen in every layer.  ``lower_layers``
        types are frozen in the lowest ``lower_fraction`` of the layers,
        rounded up to whole cells of the trainer's segment grid (cells of
        ``ceil(L / segment_max)`` layers): a pattern off that grid would
        leave rows that are frozen but still differentiated.
        """
        spec = self.traffic.get("frozen", {})
        L = self.n_layers
        out = {t: [True] * L for t in spec.get("all_layers", [])}
        lower = spec.get("lower_layers", [])
        if lower:
            cell = -(-L // int(self.traffic["trainer"]["segment_max"]))
            n = math.floor(L * float(spec["lower_fraction"]))
            n = min(-(-n // cell) * cell, L)
            for t in lower:
                out[t] = [i < n for i in range(L)]
        return out


def load_cell(name: str) -> Cell:
    bench = _load(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{[w['name'] for w in bench['workloads']]}")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return Cell(name=name, chips=int(entry["chips"]),
                config=_load(ROOT / cfg["file"]),
                traffic=_load(BENCH_DIR / "traffic"
                              / f"{entry['traffic']}.json"),
                pair=_load(BENCH_DIR / "cells" / f"{name}.json"))


def model_config(cell: Cell):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.config import ModelConfig
    c = cell.config
    return ModelConfig(
        name=c["name"], family="dense", n_layers=cell.n_layers,
        d_model=int(c["hidden_size"]), n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]), vocab=cell.vocab,
        head_dim=int(c["head_dim"]), rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["compute_dtype"], param_dtype=c["param_dtype"])


def train_config(cell: Cell, seed: int):
    """The program's ``TrainConfig`` for the traffic mix."""
    from repro.config import GradESConfig, TrainConfig
    t, o = cell.traffic["trainer"], cell.traffic["optimizer"]
    g = cell.traffic["grades"]
    return TrainConfig(
        seq_len=cell.seq_len, global_batch=cell.rows, steps=int(t["steps"]),
        optimizer="adamw", lr=float(o["lr"]),
        warmup_frac=float(o["warmup_frac"]), schedule=o["schedule"],
        weight_decay=float(o["weight_decay"]), b1=float(o["b1"]),
        b2=float(o["b2"]), eps=float(o["eps"]),
        grad_clip=float(o["grad_clip"]), kernels=t["kernels"],
        sync_interval=int(t["sync_interval"]),
        prefetch_depth=int(t["prefetch_depth"]),
        segment_max=int(t["segment_max"]), remat=t["remat"],
        numerics_guard=bool(t["numerics_guard"]), seed=seed % 2**31,
        grades=GradESConfig(enabled=True, tau=float(g["tau"]),
                            alpha=float(g["alpha"]), monitor=g["monitor"],
                            normalize=bool(g["normalize"])))
