"""Host turnaround between serve steps, in milliseconds: the mean, over
the traced engine iterations, of the time from the end of
``engine/host_sync`` (the step's results are on the host) to the start
of the next ``engine/dispatch`` — the host work the idle chip waits
for.  Both are the engine's own spans (``serving/engine.py``
``_run_lane``, ``Tracer.span``) in the trace's host plane; a program
without them reads nothing."""

SYNC, DISPATCH = "engine/host_sync", "engine/dispatch"


def read(ctx):
    spans = sorted((e for e in ctx.trace.host if e.name in (SYNC, DISPATCH)),
                   key=lambda e: e.start)
    gaps, synced = [], None
    for e in spans:
        if e.name == SYNC:
            synced = e.end
        elif synced is not None:
            gaps.append(e.start - synced)
            synced = None
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
