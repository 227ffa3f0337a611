"""``correct`` at a tiny size on the CPU: a sound run passes; the control
(the reference computed in float8, put in the program's place) and a run with
the timed path broken underneath each come out not correct.  The chip-size
readings behind the cells' limits are in PERF.md (``bench/calibrate.py``)."""
import json
import os

import jax
import pytest

import calibrate
import check
import run
from helpers import tiny_cell

SEED = 2**31 + 12345


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def run_tiny(bench, traffic="ft-wave"):
    out, numbers = run.run_cell(tiny_cell(traffic), SEED, 0.5, False, bench,
                                jax.devices())
    return out, numbers


@pytest.mark.parametrize("traffic", ["ft-wave", "ft-live"])
def test_sound_run_is_correct(bench, traffic):
    out, numbers = run_tiny(bench, traffic)
    assert out["correct"], numbers
    assert out["attempted"] >= 32 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_control_is_not_correct():
    cell = tiny_cell()
    _, gen = calibrate.program_readings(cell, SEED)
    ref = calibrate.reference_readings(cell, SEED, gen)
    ctl = calibrate.reference_readings(cell, SEED, gen, "fp8")
    numbers = check.readings(ctl, ref)
    assert not check.judge(numbers, cell.pair["limits"]), numbers


@pytest.mark.parametrize("fault", sorted(calibrate.FAULTS))
def test_broken_step_is_not_correct(bench, fault, monkeypatch):
    import repro.train.loop as loop
    monkeypatch.setattr(loop, "make_multi_step",
                        calibrate.FAULTS[fault](loop.make_multi_step))
    out, numbers = run_tiny(bench)
    assert not out["correct"], numbers
