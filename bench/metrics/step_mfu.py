"""step_mfu: the whole step's share of the chips' peak bf16 FLOP/s, in %.

Model FLOPs per token (forward and backward, no recomputation: the
``flops_per_token`` of the configuration's reference in
``bench/models/<family>.py``) times the tokens completed in the traced
window, over the window's seconds and the peak of every chip used."""
import reference


def read(ctx):
    if ctx["peaks"] is None:
        return None
    c, mix = ctx["config"], ctx["mix"]
    flops = reference.family(c).flops_per_token(c, mix["seq"]) * ctx["tokens"]
    return 100.0 * flops / ctx["window_s"] / (ctx["peaks"].flops * ctx["chips"])
