"""fwd_bwd_ms: device milliseconds per round in span ``laq.fwd_bwd``, the
loss and its gradient at every evaluation point (``loss_and_grads`` in
``launch/train.py``: forward and backward, each microbatch), averaged over
the chips used (profiler trace; ``bench/scopes.py``)."""
import scopes

read = scopes.reader("laq.fwd_bwd")
