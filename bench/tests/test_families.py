"""Model families (``bench/families/``): the dense decoder behind the
harness's entry points gives every count, ``ModelConfig`` and weight it gave
before it moved there; and a family that exists only in this test, with
weights per layer and expert, joins the harness by its configuration file
alone."""
import dataclasses
import hashlib
import json
import sys
import types

import jax
import numpy as np
import pytest

import cell as cell_module
import check
import flops
import program
import weights
from cell import load_cell, model_config, train_config
from helpers import tiny_cell
from repro.config import ModelConfig, MoEConfig

SEED = 2**31 + 12345


def dense_shapes(L, d, q, kv, f, V, tied):
    out = {"embed": ((V, d), -1),
           "layers": {"attn_norm": ((L, d), None),
                      "wq": ((L, d, q), -2), "wk": ((L, d, kv), -2),
                      "wv": ((L, d, kv), -2), "wo": ((L, q, d), -2),
                      "mlp_norm": ((L, d), None),
                      "w_gate": ((L, d, f), -2), "w_up": ((L, d, f), -2),
                      "w_down": ((L, f, d), -2)},
           "final_norm": ((d,), None)}
    if not tied:
        out["lm_head"] = ((d, V), -2)
    return out


QWEN3 = ModelConfig(name="qwen3-0.6b", family="dense", n_layers=28,
                    d_model=1024, n_heads=16, n_kv_heads=8, d_ff=3072,
                    vocab=151936, head_dim=128, rope_theta=1e6,
                    norm_eps=1e-6, tie_embeddings=True, dtype="bfloat16",
                    param_dtype="float32")
YI = ModelConfig(name="yi-9b", family="dense", n_layers=3, d_model=4096,
                 n_heads=32, n_kv_heads=4, d_ff=11008, vocab=8000,
                 head_dim=128, rope_theta=5e6, norm_eps=1e-6,
                 tie_embeddings=False, dtype="bfloat16",
                 param_dtype="float32")

#: what each cell read before the dense decoder moved into its family
BEFORE = {
    "qwen3-0.6b.ft-wave": dict(
        step=31152068886528, attn=68753031168,
        flash=(101187584, 135266304, 135266304),
        grades=(1610612736, 5637144576), share=0.542857, model=QWEN3,
        shapes=dense_shapes(28, 1024, 2048, 1024, 3072, 151936, True)),
    "yi-9b.ft-wave": dict(
        step=50432713949184, attn=275012124672,
        flash=(304087040, 440401920, 339738624),
        grades=(2617245696, 9160359936), share=0.369697, model=YI,
        shapes=dense_shapes(3, 4096, 4096, 512, 11008, 8000, False)),
    "qwen3-0.6b.ft-live": dict(
        step=17534539530240, attn=34376515584,
        flash=(50593792, 67633152, 67633152),
        grades=(3523215360, 12331253760), share=0.0, model=QWEN3,
        shapes=dense_shapes(28, 1024, 2048, 1024, 3072, 151936, True)),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_dense_cells_read_as_before(name):
    want, cell = BEFORE[name], load_cell(name)
    assert flops.step_flops(cell) == want["step"]
    assert flops.attention_flops_per_call(cell) == want["attn"]
    f = flops.flash_bytes_per_call(cell)
    assert (f["flash_fwd"], f["flash_dq"], f["flash_dkv"]) == want["flash"]
    g = flops.grades_bytes_per_step(cell)
    assert (g["grades_norm"], g["masked_adamw"]) == want["grades"]
    assert flops.frozen_share(cell) == pytest.approx(want["share"], abs=1e-6)
    assert model_config(cell) == want["model"]
    assert weights.shapes(cell.config) == want["shapes"]


def fingerprint(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in sorted(jax.tree_util.tree_leaves_with_path(tree),
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_dense_weights_for_a_seed_are_bit_identical():
    assert fingerprint(weights.make(tiny_cell().config, SEED)) == \
        "334f4e9ebc31b5b8b952af5efcbee83a9e518e8fa0820b4a7efd5b9418b2ac64"


# ---------------------------------------------- a family of this test alone
L, E, D, F, V = 4, 3, 16, 32, 64
FAMILY = "toy_experts"
TRAFFIC = cell_module.BENCH_DIR / "traffic"


def toy_family() -> types.ModuleType:
    """Weights per layer and expert: one ``(L, E, d, f)`` monitored leaf,
    frozen per layer and expert."""
    fam = types.ModuleType(f"families.{FAMILY}")
    fam.model_config = lambda cell: ModelConfig(
        name=cell.config["name"], family="moe", n_layers=L, d_model=D,
        vocab=V, moe=MoEConfig(n_experts=E, top_k=2, d_ff=F))
    fam.shapes = lambda config: {
        "embed": ((V, D), -1),
        "layers": {"mlp_norm": ((L, D), None), "w_up": ((L, E, D, F), -2)},
        "final_norm": ((D,), None)}
    fam.step_flops = lambda cell: 6 * cell.rows * cell.seq_len * L * E * D * F

    def frozen_masks(cell):
        # the layers the traffic freezes, and expert 0 of every layer
        rows = np.asarray(cell.frozen_rows()["w_up"], bool)
        mask = np.repeat(rows[:, None], E, axis=1)
        mask[:, 0] = True
        return {"layers/w_up": mask}
    fam.frozen_masks = frozen_masks
    return fam


def write_tree(root, config):
    """A benchmark of one cell, ``toy.wave``, under ``root``."""
    bench = root / "bench"
    for d in ("configs", "traffic", "cells"):
        (bench / d).mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.wave", "config": "toy",
                       "traffic": "wave", "chips": 1}]}))
    (bench / "configs" / "toy.json").write_text(json.dumps(config))
    traffic = json.loads((TRAFFIC / "ft-wave.json").read_text())
    traffic.update(seq_len=32, frozen={"lower_layers": ["w_up"],
                                       "lower_fraction": 0.5})
    (bench / "traffic" / "wave.json").write_text(json.dumps(traffic))
    (bench / "cells" / "toy.wave.json").write_text(json.dumps(
        {"rows_per_chip": 2, "limits": {}}))


@pytest.fixture
def toy_tree(tmp_path, monkeypatch):
    monkeypatch.setattr(cell_module, "ROOT", tmp_path)
    monkeypatch.setattr(cell_module, "BENCH_DIR", tmp_path / "bench")
    monkeypatch.setitem(sys.modules, f"families.{FAMILY}", toy_family())
    return tmp_path


TOY = {"name": "toy", "num_hidden_layers": L, "vocab_size": V,
       "param_dtype": "float32", "family": FAMILY}


def test_a_family_joins_by_its_configuration_file(toy_tree):
    write_tree(toy_tree, TOY)
    cell = load_cell("toy.wave")
    assert model_config(cell).moe.n_experts == E
    params = weights.make(cell.config, SEED)
    assert params["layers"]["w_up"].shape == (L, E, D, F)
    assert flops.step_flops(cell) == 6 * 2 * 32 * L * E * D * F

    state = program.build_state(cell, params, train_config(cell, SEED))
    frozen = np.asarray(state.grades.frozen["layers/w_up"])
    want = np.zeros((L, E), bool)
    want[:L // 2] = True
    want[:, 0] = True
    np.testing.assert_array_equal(frozen, want)

    # frozen_moved reads the same (L, E) flags: a live expert moves freely,
    # a frozen one is caught
    for (l, e), moved in (((3, 1), 0.0), ((3, 0), 1.0), ((1, 2), 1.0)):
        p = state.params
        p = {**p, "layers": {**p["layers"], "w_up":
                             p["layers"]["w_up"].at[l, e, 0, 0].add(1.0)}}
        got = program.first_block_readings(
            dataclasses.replace(state, params=p), params, cell)
        assert got["frozen_moved"]["layers/w_up"] == pytest.approx(moved), \
            (l, e)


@pytest.mark.parametrize("family,message", [
    (None, "no model family"), ("no_such_family", "unknown model family")])
def test_a_configuration_without_a_known_family_is_refused(
        toy_tree, family, message):
    config = {k: v for k, v in TOY.items() if k != "family"}
    if family:
        config["family"] = family
    write_tree(toy_tree, config)
    with pytest.raises(SystemExit, match=message) as e:
        load_cell("toy.wave")
    assert "bench/configs/toy.json" in str(e.value)


@pytest.mark.parametrize("rows,at", [
    ([0.0, 2.0, 4.0], "layers/w_up[2]"),
    ([[0.0, 2.0], [1.0, 4.0]], "layers/w_up[1,1]")])
def test_monitor_rows_of_any_shape_are_compared(rows, at):
    ref = {"losses": [1.0], "grad": {"a": 1.0}, "change": {"a": 1.0},
           "monitor": {"layers/w_up": rows}}
    got = np.asarray(rows) * 1.01
    got[(0,) * got.ndim] = 7.0               # a frozen row: not compared
    got[tuple(int(i) for i in at[at.index("[") + 1:-1].split(","))] *= 1.5
    prog = dict(ref, monitor={"layers/w_up": got.tolist()})
    numbers = check.readings(prog, ref)
    assert numbers["monitor_gap_at"] == at
    assert numbers["monitor_gap"] == pytest.approx(0.515)
