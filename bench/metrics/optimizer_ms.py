"""optimizer_ms: device milliseconds per round in span ``laq.optimizer``,
the optimizer's update, averaged over the chips used (profiler trace;
``bench/scopes.py``)."""
import scopes

read = scopes.reader("laq.optimizer")
