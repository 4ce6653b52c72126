"""Mean host time, in ms, of the scheduler's sampling (its
``sched.sample`` span: per-row argmax or seeded categorical draw,
stream bookkeeping) per tick that emitted a token, over the traced
window."""
import program_trace


def read(view):
    pt = program_trace.for_view(view)
    tr = view.traced
    s = program_trace.sample_seconds(pt.spans, tr.lo, tr.hi) if pt else []
    return 1e3 * sum(s) / len(s) if s else None
