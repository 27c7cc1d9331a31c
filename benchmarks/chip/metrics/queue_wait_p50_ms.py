"""p50 over every request due in the window of the time from its
scheduled arrival to its admission to a slot (the engine's own
admission stamp); a request still queued at the close counts with its
wait so far."""
from context import percentile


def read(ctx):
    t_end = ctx.win.t_end
    waits = []
    for r in ctx.due_in_window():
        t_adm = ctx.win.admitted.get(r.uid)
        waits.append(min(t_adm if t_adm is not None else t_end, t_end)
                     - r.due)
    v = percentile(waits, 50)
    return None if v is None else 1e3 * v
