"""wire_codec_ms: device milliseconds per round in span ``laq.wire.codec``,
the byte layout between the kernels' 1024-byte code rows and the payload
(their flatten, slice and reshape to the leaf, ``pack_codes_along_axis``,
``unpack_codes_along_axis``), averaged over the chips used (profiler
trace; ``bench/scopes.py``)."""
import scopes

read = scopes.reader("laq.wire.codec")
