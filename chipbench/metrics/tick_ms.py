"""Mean host time, in ms, of a ``Scheduler.tick`` that ran steps, over
the traced window (admission, prefill micro-steps, the decode step,
the logits copy to the host, sampling and slot release)."""


def read(view):
    tr = view.traced
    ticks = [t1 - t0 for t0, t1, _, bucket, _ in tr.ticks if bucket] if tr else []
    return 1e3 * sum(ticks) / len(ticks) if ticks else None
