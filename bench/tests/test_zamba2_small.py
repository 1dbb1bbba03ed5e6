"""The check that decides ``correct``, for the Zamba2 cell at a size the CPU
holds: a sound run comes out correct, and a step that returns its state
unchanged, and one that leaves half of the batch out, do not.

``small.SMALL`` has no size for the ``zamba2`` family yet, so
``test_check.py`` cannot make this cell small; the cell is made small here,
from its own configuration and traffic, at the widths below.  ``LIMITS``
are set as the cells' limits are, from readings at this size on the CPU
(seeds 5-7): sound runs read ``grad_gap_median`` 0.0027-0.0039 and
``code_mismatch`` 2.0-2.5%; the float8 control 0.032-0.039 and 23.8-25.0%;
half of the batch 0.11-0.41 and 34.6-36.0%; the state left unchanged 1 and
99.98%.  ``update_gap``: sound 0.0003-0.0006, unchanged 1.
"""
import copy

import pytest

import small
import correct
import harness
import reference
import traffic
from test_check import _run, half_batch, unchanged

CELL = "zamba2-7b.tp4.l6.laq4.seq4k"
LIMITS = {"grad_gap_median": 0.01, "update_gap": 0.05, "code_mismatch": 8.0,
          "uploads_mismatch": 0, "bits_mismatch": 0}
WIDTHS = dict(d_model=64, vocab=250, n_heads=4, n_kv_heads=4, head_dim=32,
              d_ff=96, ssm_state=16, ssm_head_dim=16, ssm_n_heads=4,
              ssm_chunk=16, adapter_rank=8, q_chunk=32, kv_chunk=16)


def small_zamba2() -> dict:
    """The cell's stage (6 layers, the last hybrid, one shared block) at
    small widths, 4 rows of 32 tokens in its 2 microbatches."""
    w = [w for w in harness.spec()["workloads"] if w["name"] == CELL][0]
    config = harness.load_json(small.BENCH / "configs" / f"{w['config']}.json")
    config.update(WIDTHS)
    mix = copy.deepcopy(traffic.load(w["traffic"]))
    mix.update(rows_per_worker=4, seq=32, pool_rounds=400)
    return dict(name="small-zamba2", chips=1, config=config, mix=mix,
                limits=dict(LIMITS))


def test_sound_run_is_correct():
    r = _run(small_zamba2())
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_fault_is_not_correct(fault):
    r = _run(small_zamba2(), fault)
    assert not r["correct"], r["checks"]


def test_control_is_not_correct():
    c = small_zamba2()
    ref = reference.run(5, c["config"], c["mix"], 1)
    ctl = reference.run(5, c["config"], c["mix"], 1, precision="fp8")
    ok, checks = correct.judge(correct.readings(ctl, ref), c["limits"])
    assert not ok, checks
