"""Small cells for the yardstick's tests on the CPU: the cells' families and
traffic at widths a test can hold.

``LIMITS`` are the check's limits at this size, set as the cells' limits
are, from readings on the CPU at this size (see PERF.md): sound runs of
the program read ``grad_gap_median`` up to 0.0028, the float8 control
0.0074 and more, half of the batch left out 0.048 and more; the state
left unchanged reads ``update_gap`` 1.  ``code_mismatch``: the program
0.40-0.48%, the control 5.5-6.7%, half of the batch 23-26%, the state left
unchanged 99.98% (seeds 5-7, both cells).  The worst leaf's ``grad_gap``
is not compared: the control reads only 2.9 times the program there."""
import copy
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import traffic  # noqa: E402

LIMITS = {"grad_gap_median": 0.005, "update_gap": 0.05, "code_mismatch": 2.0,
          "uploads_mismatch": 0, "bits_mismatch": 0}

SMALL = {
    "mamba2": dict(n_layers=2, d_model=64, vocab=250, ssm_state=16,
                   ssm_head_dim=16, ssm_chunk=16),
    "dense": dict(n_layers=2, d_model=64, vocab=250, n_heads=4, n_kv_heads=4,
                  head_dim=16, d_ff=128, q_chunk=32, kv_chunk=16),
}
CONFIGS = {"mamba2": "mamba2-130m", "dense": "stablelm-2-1.6b.l4.v25k"}


def small_cell(family: str, rows: int = 2, seq: int = 32,
               microbatches: int = 1, traffic_name: str = "laq4.tok2k") -> dict:
    config = harness.load_json(BENCH / "configs" / f"{CONFIGS[family]}.json")
    config.update(SMALL[family])
    mix = copy.deepcopy(traffic.load(traffic_name))
    mix.update(rows_per_worker=rows, seq=seq, microbatches=microbatches,
               pool_rounds=400)
    return dict(name=f"small-{family}", chips=1, config=config, mix=mix,
                limits=dict(LIMITS))


def small_of(cell_name: str) -> dict:
    """Cell ``cell_name`` of BENCHMARK.json at a size the CPU holds: its
    family's small widths, at most 4 rows of 32 tokens a round, its own
    traffic otherwise (microbatches, checked rounds, the LAQ job)."""
    w = [w for w in harness.spec()["workloads"] if w["name"] == cell_name][0]
    family = harness.load_json(BENCH / "configs" / f"{w['config']}.json")["family"]
    mix = traffic.load(w["traffic"])
    return small_cell(family, rows=min(mix["rows_per_worker"], 4),
                      microbatches=mix["microbatches"],
                      traffic_name=w["traffic"])

# a CPU compile written to the persistent cache warns when read back on
# another host; the tests compile afresh
import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
