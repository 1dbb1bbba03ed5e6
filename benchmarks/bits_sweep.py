"""Paper supp: communication cost vs quantization bits b."""
from __future__ import annotations

from repro.core import StrategyConfig, run_gradient_based

from .common import PAPER_CRITERION, logreg_init, logreg_loss, make_dataset


def run(out_rows, results):
    workers, full = make_dataset()
    loss_fn = logreg_loss(full[0].shape[0])

    # ---- bits sweep (paper supp: b in {2,4,8}) ----
    sweep = {}
    for b in (2, 4, 8):
        r = run_gradient_based(loss_fn, logreg_init(), workers,
                               StrategyConfig(kind="laq", bits=b,
                                              criterion=PAPER_CRITERION),
                               steps=400, alpha=2.0)
        sweep[b] = dict(bits=float(r.cum_bits[-1]),
                        rounds=int(r.cum_uploads[-1]),
                        final_loss=float(r.loss[-1]))
        out_rows.append((f"bits_sweep_b{b}", float(r.cum_bits[-1]),
                         f"rounds={sweep[b]['rounds']};loss={sweep[b]['final_loss']:.2e}"))
    results["bits_sweep"] = sweep

    checks = {"fewer bits per round with smaller b":
              sweep[2]["bits"] < sweep[4]["bits"] < sweep[8]["bits"]}
    results["bits_sweep/claims"] = checks
    return checks
