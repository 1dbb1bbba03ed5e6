"""The check that decides ``correct``, at a size a test run holds on the CPU.

For each cell of BENCHMARK.json, at its family's small widths: a sound run
comes out correct; the control (the reference in float8 in the program's
place) and every fault a one-chip training cell can have come out not
correct: a step that returns its state unchanged, and half of the batch
left out with the mean taken over the rest.  A one-chip cell has no
exchange between chips to leave out, and a training step produces no
token or answer to alter.  The faults break the timed path underneath the
harness, which runs everything else of a run but the look for a chip.
The dense family, whose cell is out of BENCHMARK.json, keeps its sound run:
its plain reference matches the program on the CPU.
"""
import time

import jax
import pytest

import small
import correct
import harness
import reference

CELLS = [w["name"] for w in harness.spec()["workloads"]]


def _run(c, fault=None, seed=5):
    return harness.run(c["name"], seed, 0.2, False, t_start=time.perf_counter(),
                       the_cell=c, metric_names=["tokens_per_s"],
                       require_tpu=False, fault=fault, log=lambda m: None)


def unchanged(step):
    def broken(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return broken


def half_batch(step):
    def broken(state, batch):
        rows = batch["tokens"].shape[0]
        if rows > 1:
            half = jax.tree.map(lambda x: x[:rows // 2], batch)
        else:
            half = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batch)
        return step(state, half)
    return broken


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = _run(small.small_of(cell))
    assert r["correct"], r["checks"]


def test_dense_sound_run_is_correct():
    r = _run(small.small_cell("dense"))
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_fault_is_not_correct(cell, fault):
    r = _run(small.small_of(cell), fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    c = small.small_of(cell)
    ref = reference.run(5, c["config"], c["mix"], 1)
    ctl = reference.run(5, c["config"], c["mix"], 1, precision="fp8")
    ok, checks = correct.judge(correct.readings(ctl, ref), c["limits"])
    assert not ok, checks
