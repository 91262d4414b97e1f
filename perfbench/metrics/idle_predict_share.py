"""Prediction and prefetch: device-idle time while one draft token's expert
prediction, lookups and prefetch submissions (``spmoe.predict``, inside
``spmoe.draft``) was the innermost serving stage, as a % of the traced
window.  Ownership is worked out over the stage spans and this one, so the
other stages' shares are read as before; None without the span."""
from perfbench import spans
from perfbench.trace import union

SPAN = "spmoe.predict"


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or not run.trace_window_s:
        return None
    stages = [o for o in tr.host if o.name in spans.STAGES + (SPAN,)]
    if not any(o.name == SPAN for o in stages):
        return None
    own = spans.owned(stages).get(SPAN, [])
    ops = next(iter(tr.devices.values()))
    busy = union((o.start_ns, o.start_ns + o.dur_ns) for o in ops)
    idle_ns = sum(e - s for s, e in own) - spans.overlap_ns(own, busy)
    return 100.0 * idle_ns / 1e9 / run.trace_window_s
