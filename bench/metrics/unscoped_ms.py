"""unscoped_ms: device milliseconds per round in operations that belong to
no ``laq.*`` span or that the step's HLO does not hold, what the partition
into spans misses, averaged over the chips used (profiler trace;
``bench/scopes.py``)."""
import scopes

read = scopes.reader(None)
