"""lazy_rule_ms: device milliseconds per round in span ``laq.lazy_rule``,
the lazy rule (``worker_update``: criterion moments, skip decision, clocks,
bits; the parameter motion and its history; the round's counters),
averaged over the chips used (profiler trace; ``bench/scopes.py``)."""
import scopes

read = scopes.reader("laq.lazy_rule")
