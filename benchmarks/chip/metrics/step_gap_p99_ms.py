"""p99 over every interval between successive engine steps seen by a
live request (from its admission to its harvest or the window's close),
pooled over all requests: the gaps a streaming client sees, other rows'
admissions included."""
import numpy as np

from context import percentile


def read(ctx):
    steps = np.asarray(ctx.win.step_times)
    gaps = []
    for r in ctx.win.records:
        t_adm = ctx.win.admitted.get(r.uid)
        if t_adm is None:
            continue
        end = min(r.done_at or ctx.win.t_end, ctx.win.t_end)
        mine = steps[(steps > t_adm) & (steps <= end)]
        gaps.extend(np.diff(mine))
    v = percentile(gaps, 99)
    return None if v is None else 1e3 * v
