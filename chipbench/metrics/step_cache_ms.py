"""Mean device time, in ms, per execution of a scheduler step program
(``jit_step*``) in the traced window, of its whole-cache passes: the
operations under the ``mask_slots`` and ``cache_write`` named scopes,
each by its self time. Logs whether the program's own step kinds (its
``sched.step`` spans, in issue order) pair the step executions as the
harness's reconstruction from ``TickInfo`` does."""
import sys

import program_trace

STEP_PREFIX = "jit_step"
SLACK = 1e-3      # host and device clocks agree to a few microseconds


def read(view):
    pt = program_trace.for_view(view)
    tr = view.traced
    if pt is None:
        return None
    expected = []
    for _, _, micro, bucket, _ in tr.ticks:
        expected += ["prefill"] * micro + (["decode"] if bucket else [])
    kinds = program_trace.step_kinds(pt.spans, tr.lo, tr.hi)
    if kinds:
        agree = "agree" if kinds == expected else "DISAGREE"
        print(f"trace: program step kinds {agree} with the harness's "
              f"({len(kinds)} and {len(expected)} steps; scopes from "
              f"{pt.scope_source or 'nothing'})", file=sys.stderr, flush=True)
    runs = [m for m in tr.trace.modules if m[2].startswith(STEP_PREFIX)
            and m[0] >= tr.lo - SLACK and m[1] <= tr.hi + SLACK]
    secs = program_trace.scope_seconds(pt.ops, tr.lo, tr.hi)
    if not runs or not secs:
        return None
    return 1e3 * sum(secs.values()) / len(runs)
