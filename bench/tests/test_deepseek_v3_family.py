"""The DeepSeek-V3 family (``bench/families/deepseek_v3.py``) and its plain
reference: the Moonlight cell's counts equal numbers derived here by hand; a
tiny ``deepseek_v3`` cell (``data/tiny_deepseek_v3.json``) run through the
program and the reference on the CPU is ``correct``, and with the planted
``half_batch`` or ``half_seq`` fault it is not; the traffic still reads through
``Cell.frozen_rows``; and the reference takes nothing from the program or
the family."""
import ast
import json
import os

import jax
import numpy as np
import pytest

import calibrate
import calibrate_one_row
import flops
import run
import weights
from cell import BENCH_DIR, Cell, load_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "moonlight-16b-a3b.ft-expert-wave"
SEED = 2**31 + 12345

# Moonlight-16B-A3B, one row of 8192 tokens a step, cut as its configuration
# file says: d 2048, 16 heads of q/k 128 + 64 and v 128, latent 512, dense
# MLP 11264, 8 held experts of 1408, shared 2 x 1408, 1 + 5 layers, 20480
# vocabulary rows
MLA = 2048 * 16 * 192 + 2048 * (512 + 64) + 512 * 16 * 256 + 16 * 128 * 2048
DENSE_MLP = 3 * 2048 * 11264
EXPERT = 3 * 2048 * 1408
EXPERT_LAYER = 2048 * 64 + 8 * EXPERT + 3 * 2048 * 2816
EMBED_HEAD = 2 * 20480 * 2048
FROZEN = 6 * 2048 * 16 * 192 + 2 * 8 * EXPERT + 3 * 4 * EXPERT
S = 8192
PAIRS = S * (S + 1) // 2
QK, V, ROW = S * 16 * 192 * 2, S * 16 * 128 * 2, S * 16 * 4


def test_counts_of_the_moonlight_cell():
    cell = load_cell(CELL)

    def leaves(tree):
        for name, x in tree.items():
            yield from leaves(x) if isinstance(x, dict) else [(name, x[0])]

    matrices = sum(int(np.prod(shape)) for name, shape in
                   leaves(weights.shapes(cell.config))
                   if not name.endswith("norm") and name != "router_bias")
    assert 6 * MLA + DENSE_MLP + 5 * EXPERT_LAYER + EMBED_HEAD \
        == 668_860_416 == matrices
    monitored = 6 * MLA + DENSE_MLP + 5 * EXPERT_LAYER
    assert flops.frozen_share(cell) == pytest.approx(FROZEN / monitored)
    assert round(flops.frozen_share(cell), 4) == 0.4786
    assert flops.attention_flops_per_call(cell) == \
        2 * 16 * (192 + 128) * PAIRS == 343_639_326_720
    assert flops.flash_bytes_per_call(cell) == {
        "flash_fwd": 2 * QK + 2 * V + ROW,          # q, k, v in; o, lse out
        "flash_dq": 3 * QK + 2 * V + 2 * ROW,       # + do, D in; dq out
        "flash_dkv": 3 * QK + 3 * V + 2 * ROW}      # dk, dv out
    live = monitored - FROZEN
    assert flops.grades_bytes_per_step(cell) == {
        "grades_norm": 8 * live, "masked_adamw": 28 * live}
    assert cell.family.expert_flops_per_pick(cell) == 6 * 2048 * 1408


def test_the_traffic_reads_through_frozen_rows():
    cell = load_cell(CELL)
    assert cell.frozen_rows() == {"wq": [True] * 6}
    masks = cell.family.frozen_masks(cell)
    assert masks["dense_layers/wq"].tolist() == [True]
    assert masks["layers/wq"].tolist() == [True] * 5
    want = np.zeros((5, 8), bool)
    want[:2] = True
    want[2:, :4] = True
    for t in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(masks[f"layers/{t}"], want)
    assert set(masks) == {"dense_layers/wq", "layers/wq", "layers/w_gate",
                          "layers/w_up", "layers/w_down"}


def test_the_reference_imports_nothing_of_the_program_or_family():
    path = os.path.join(BENCH_DIR, "references", "deepseek_v3.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert names and not [n for n in names
                          if n.split(".")[0] in ("repro", "families")], names


def tiny_cell() -> Cell:
    with open(os.path.join(HERE, "data", "tiny_deepseek_v3.json")) as f:
        cfg = json.load(f)
    with open(BENCH_DIR / "traffic" / "ft-expert-wave.json") as f:
        mix = json.load(f)
    mix["seq_len"] = cfg["seq_len"]
    mix["frozen"]["expert_rows"] = cfg["expert_rows"]
    return Cell(name="tiny.ft-expert-wave", chips=1, config=cfg, traffic=mix,
                pair={"rows_per_chip": cfg["rows_per_chip"],
                      "limits": cfg["limits"]})


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_a_tiny_cell_is_correct(bench):
    out, numbers = run.run_cell(tiny_cell(), SEED, 0.5, False, bench,
                                jax.devices())
    assert out["correct"], numbers
    assert out["attempted"] >= 32 and out["failed"] == 0
    # the correction bias and the wholly frozen wq train nowhere
    assert {"layers/router_bias", "layers/wq",
            "dense_layers/wq"} <= set(numbers["left_out"])


def test_a_tiny_cell_with_half_the_batch_is_not_correct(bench, monkeypatch):
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "make_multi_step",
                        calibrate.half_batch(loop.make_multi_step))
    out, numbers = run.run_cell(tiny_cell(), SEED, 0.5, False, bench,
                                jax.devices())
    assert not out["correct"], numbers


def test_a_tiny_cell_with_half_of_each_row_is_not_correct(bench, monkeypatch):
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "make_multi_step",
                        calibrate_one_row.half_seq(loop.make_multi_step))
    out, numbers = run.run_cell(tiny_cell(), SEED, 0.5, False, bench,
                                jax.devices())
    assert not out["correct"], numbers


def test_expert_matmul_roofline_reads_the_marked_picks():
    """Two drains' marks in the window, named as ``repro.tracing.mark``
    writes them: 3 passes of every pick (remat "full") and dW of the live
    ones, over the seconds of the ``gmm``/``tgmm`` ops alone."""
    from types import SimpleNamespace
    cell = load_cell(CELL)
    span = "/repro/train/expert_load#step={},assigned=600,assigned_live=400," \
        "busiest=100"
    host = [(1.0, 1.0, span.format(0)), (2.0, 2.0, span.format(8)),
            (0.5, 3.0, "/repro/train/drain#step=0")]
    ops = {0: [(0.0, 4e6, "%gmm.3 = bf16[...] custom-call(...)"),
               (4e6, 5e6, "%transpose_jvp_jit_tgmm_.1 = f32[...] "
                "custom-call(...)"),
               (5e6, 9e6, "%flash_fwd.2 = bf16[...] custom-call(...)")]}
    ctx = {"cell": cell, "peaks": {"bf16_flops": 197e12},
           "trace": SimpleNamespace(host=host, ops=ops)}
    read = run.metric_reader("expert_matmul_roofline")
    want = 100 * 2 * (3 * 600 + 400) * 6 * 2048 * 1408 / 197e12 / 5e-3
    assert read(ctx) == pytest.approx(want)
    # a program that marks nothing while the kernels run reads nothing
    ctx["trace"] = SimpleNamespace(host=host[2:], ops=ops)
    assert read(ctx) is None
