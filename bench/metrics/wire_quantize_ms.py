"""wire_quantize_ms: device milliseconds per round in span
``laq.wire.quantize``, the wire's radius and quantize passes
(``core/wire.py``: the Pallas kernels with their operand casts and pads,
wherever the round calls them), averaged over the chips used (profiler
trace; ``bench/scopes.py``)."""
import scopes

read = scopes.reader("laq.wire.quantize")
