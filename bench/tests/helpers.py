"""A tiny cell (``data/tiny.json``) under the repository's traffic mixes."""
import json
import os

from cell import BENCH_DIR, Cell

HERE = os.path.dirname(os.path.abspath(__file__))


def tiny_cell(traffic: str = "ft-wave", **config) -> Cell:
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(config)
    with open(BENCH_DIR / "traffic" / f"{traffic}.json") as f:
        mix = json.load(f)
    mix["seq_len"] = cfg["seq_len"]
    return Cell(name=f"tiny.{traffic}", chips=1, config=cfg, traffic=mix,
                pair={"rows_per_chip": cfg["rows_per_chip"],
                      "limits": cfg["limits"]})
