"""Required FLOPs and bytes against hand sums."""
import flops
from helpers import tiny_cell


def test_one_layer_step_flops_by_hand():
    # one layer, d 64, 4/2 heads of 16, ff 128, vocab 256, tied; 2 rows of 64
    # tokens; ft-wave freezes wq, wk everywhere and the MLP in the lowest
    # half (floor(1 * 0.5) = 0 layers here, so the MLP stays live)
    cell = tiny_cell(num_hidden_layers=1)
    T = 2 * 64
    wq, wk, wv, wo = 64 * 64, 64 * 32, 64 * 32, 64 * 64
    mlp = 3 * 64 * 128
    head = 64 * 256
    weights = wq + wk + wv + wo + mlp
    live = wv + wo + mlp
    attn = 4 * 4 * 16 * (2 * 64 * 65 // 2)          # QK^T and PV, causal
    want = 2 * T * (weights + head) * 2 + 2 * T * (live + head) + 3 * attn
    assert flops.step_flops(cell) == want
    assert flops.attention_flops_per_call(cell) == attn


def test_frozen_share_and_grades_bytes():
    cell = tiny_cell()                  # 4 layers: MLP frozen in layers 0, 1
    sizes = flops.matrix_sizes(cell)
    per_layer = sum(sizes.values())
    frozen = 4 * (sizes["wq"] + sizes["wk"]) + 2 * 3 * 64 * 128
    assert flops.frozen_share(cell) == frozen / (4 * per_layer)
    live = 4 * per_layer - frozen
    assert flops.grades_bytes_per_step(cell) == {
        "grades_norm": 8 * live, "masked_adamw": 28 * live}
    assert flops.frozen_share(tiny_cell("ft-live")) == 0


def test_flash_bytes_read_and_write_each_operand_once():
    cell = tiny_cell()
    T = 2 * 64
    q, kv, row = T * 4 * 16 * 2, T * 2 * 16 * 2, T * 4 * 4
    got = flops.flash_bytes_per_call(cell)
    assert got["flash_fwd"] == q + 2 * kv + q + row
    assert got["flash_dq"] == 3 * q + 2 * kv + 2 * row
    assert got["flash_dkv"] == 2 * q + 4 * kv + 2 * row
