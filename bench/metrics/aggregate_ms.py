"""aggregate_ms: device milliseconds per round in span ``laq.aggregate``,
the exchange, dequantize and masked sum of the packed wire (its quantize
and codec apart) and the server aggregate's add and store, averaged over
the chips used (profiler trace; ``bench/scopes.py``)."""
import scopes

read = scopes.reader("laq.aggregate")
