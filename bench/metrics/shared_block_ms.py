"""shared_block_ms: device milliseconds per round in span
``laq.shared_block``, a hybrid model's shared attention block with its
invocation's adapter and linear (``models/stack.py shared_block``: forward,
recomputation and backward, inside ``laq.fwd_bwd``), averaged over the
chips used (profiler trace; ``bench/scopes.py``).  None where the step has
no such span."""
import scopes

SPAN = "laq.shared_block"


def read(ctx):
    ms = scopes.per_round_ms(ctx)
    if ms is None or SPAN not in ms:
        return None
    return ms[SPAN]
