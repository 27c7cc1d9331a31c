"""Share of the serve-step program's device time spent identifying the
rows to recompute: the operations under the ``spa_identify`` scope of
``core/spa_layer.py`` (the h·(1+norm) input pass, the rank-r proxy
kernel, the tail masking and the top-k selection), over the
serve-step program's time.

A TPU trace names each operation by its HLO text; the operation's
scope path (``jit(_serve_step)/while/body/closed_call/spa_identify/
mul:``) is the ``tf_op`` stat of its event *metadata*, which
``jax.profiler.ProfileData`` does not expose.  So this reader reads the
run's ``.xplane.pb`` itself, in the protobuf wire format, for the few
fields it needs: each device plane's event metadata (name, ``tf_op``,
``program_id``).  Time is the union of the matched operations, so a
loop's envelope event never counts twice.  A program without the
scope (one built before it) reads nothing.
"""
import os
import re

import xplane

SCOPE = "/spa_identify/"
HERE = os.path.dirname(os.path.abspath(__file__))


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of one message: an int for a varint, a
    (start, end) span for a length-delimited field; fixed-width
    fields are skipped."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in the trace")
        yield key >> 3, val


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key = val = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def op_scopes(path, program_ids):
    """{operation's HLO text: its tf_op} for the device operations of
    the programs ``program_ids`` in the XSpace file ``path``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:                                  # XSpace.planes
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:                             # XPlane.name
                name = _text(buf, v)
            elif pf == 4:                           # event_metadata
                metas.append(_map_entry(buf, v)[1])
            elif pf == 5:                           # stat_metadata
                sid, sm = _map_entry(buf, v)
                for sf, sv in _fields(buf, *sm):
                    if sf == 2:                     # XStatMetadata.name
                        stat_names[sid] = _text(buf, sv)
        if not xplane.DEVICE_PLANE.match(name):
            continue
        for meta in metas:
            op, stats = None, {}
            for mf, mv in _fields(buf, *meta):
                if mf == 2:                         # XEventMetadata.name
                    op = _text(buf, mv)
                elif mf == 5:                       # XEventMetadata.stats
                    sid, val = None, None
                    for xf, xv in _fields(buf, *mv):
                        if xf == 1:
                            sid = xv
                        elif xf in (3, 4):          # uint64, int64
                            val = xv
                        elif xf == 5:               # str_value
                            val = _text(buf, xv)
                        elif xf == 7:               # ref_value
                            val = stat_names.get(xv)
                    stats[stat_names.get(sid)] = val
            tf_op = stats.get("tf_op")
            if op and tf_op and stats.get("program_id") in program_ids:
                out.setdefault(op, tf_op)
    return out


def trace_file(ctx):
    """The run's trace: ``run.py`` records it under ``.traces/<cell>``
    beside the ``metrics`` directory this reader was loaded from."""
    tdir = os.path.join(os.path.dirname(HERE), ".traces", ctx.cell["name"])
    try:
        return xplane.find_file(tdir)
    except FileNotFoundError:
        return None


def share(ctx, path):
    mods = ctx.step_modules()
    step = sum(e.dur for e in mods)
    if path is None or step <= 0:
        return None
    ids = {int(m.group(1)) for m in
           (re.search(r"\((\d+)\)$", e.name) for e in mods) if m}
    scopes = op_scopes(path, ids)
    ops = [e for e in ctx.step_ops() if SCOPE in scopes.get(e.name, "")]
    if not ops:
        return None
    return 100.0 * sum(b - a for a, b in xplane.union(ops)) / step


def read(ctx):
    return share(ctx, trace_file(ctx))
