"""Mean host time, in ms, of a ``Scheduler.tick`` that ran steps, less
its wait on the device for the logits (``sched.tick`` less its
``sched.sync``): admission, step dispatch and the cache copies around
it, sampling and release, over the traced window."""
import program_trace


def read(view):
    pt = program_trace.for_view(view)
    tr = view.traced
    t = program_trace.tick_host_seconds(pt.spans, tr.lo, tr.hi) if pt else []
    return 1e3 * sum(t) / len(t) if t else None
