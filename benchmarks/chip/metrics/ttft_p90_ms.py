"""p90 over every request due in the window of the time from its
scheduled arrival to its first committed token.  A request with no
token when the window closes counts with its wait so far."""
from context import percentile


def read(ctx):
    t_end = ctx.win.t_end
    waits = []
    for r in ctx.due_in_window():
        first = r.commits[0][0] if r.commits else t_end
        waits.append(min(first, t_end) - r.due)
    v = percentile(waits, 90)
    return None if v is None else 1e3 * v
