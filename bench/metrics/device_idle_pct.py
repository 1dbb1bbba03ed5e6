"""device_idle_pct: the share of the traced window in which no operation ran
on the device, in %, averaged over the chips used (profiler trace)."""
import traces as trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    t0, t1 = ctx["span"]
    busy = [trace.busy_ns(trace.clip(tr.devices[p], t0, t1))
            for p in ctx["planes"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (t1 - t0))
