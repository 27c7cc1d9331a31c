"""The readers of the program's own names, on a small recorded trace in
the TPU layout (``trace_scoped.textproto``): two serve steps of 10 ms
whose operations carry their scope paths as the ``tf_op`` stat of
their event metadata (one by reference), a prefill program that shares
an operation's text, and the engine's ``engine/*`` host spans."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path.insert(0, CHIP)

import context  # noqa: E402
import xplane  # noqa: E402
from cell import WindowResult  # noqa: E402

CELL = "llada-8b-l8-rho1.blockwise-offline"


def _serialized(name):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, name)) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


def _trace(name):
    from jax.profiler import ProfileData
    return xplane.from_profile(ProfileData.from_serialized_xspace(
        _serialized(name)))


def _ctx(tr):
    with open(os.path.join(CHIP, "configs", "llada-8b-l8-rho1.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(CHIP, "traffic", "blockwise-offline.json")) as f:
        mix = json.load(f)
    win = WindowResult(0.0, 1.0, 0, [], [], {})
    return context.Ctx({"name": CELL}, cfg, mix, win, 1.0, "TPU v5 lite",
                       trace=tr)


def _write(tmp, name):
    """The fixture as ``run.py`` leaves a trace: an ``.xplane.pb`` under
    ``<dir>/plugins/profile/<time>/``."""
    out = os.path.join(tmp, "plugins", "profile", "1")
    os.makedirs(out)
    path = os.path.join(out, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(_serialized(name))
    return path


def _identify():
    spec = importlib.util.spec_from_file_location(
        "identify_share", os.path.join(CHIP, "metrics", "identify_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def scoped():
    return _trace("trace_scoped.textproto")


def test_op_scopes_read_the_metadata_of_one_program(tmp_path):
    mod = _identify()
    path = _write(str(tmp_path), "trace_scoped.textproto")
    serve = mod.op_scopes(path, {3348512332714575758})
    assert len(serve) == 9                      # the while has no tf_op
    assert serve["%sort.2 = (f32[4,768]{1,0}, s32[4,768]{1,0}) sort("
                 "f32[4,768]{1,0} %s, s32[4,768]{1,0} %i), dimensions={1}"
                 ].endswith("/spa_identify/top_k:")   # by reference
    copy = "%copy.5 = s32[4,768]{1,0} copy(s32[4,768]{1,0} %tokens)"
    assert copy not in serve
    assert mod.op_scopes(path, {777})[copy] == \
        "jit(prefill)/spa_identify/copy:"


def test_identify_share_reads_the_scope(scoped, tmp_path):
    """2 ms of each 10 ms step under ``spa_identify`` (input pass, proxy
    kernel, top-k); the serve step's copy whose text a prefill op under
    an identify scope shares stays out."""
    ctx = _ctx(scoped)
    path = _write(str(tmp_path), "trace_scoped.textproto")
    assert _identify().share(ctx, path) == pytest.approx(20.0)


def test_identify_share_finds_the_run_s_trace(scoped, tmp_path):
    """``read`` takes the trace ``run.py`` records beside the readers."""
    bench = tmp_path / "chip"
    shutil.copytree(os.path.join(CHIP, "metrics"), bench / "metrics")
    ctx = _ctx(scoped)
    read = context.reader(str(bench / "metrics"), "identify_share")
    assert read(ctx) is None                    # no trace recorded
    _write(str(bench / ".traces" / CELL), "trace_scoped.textproto")
    assert read(ctx) == pytest.approx(20.0)


def test_identify_share_is_none_without_scopes(tmp_path):
    """A program built before the scopes (``trace_small``) reads
    nothing, as does a trace without serve steps."""
    mod = _identify()
    path = _write(str(tmp_path), "trace_small.textproto")
    assert mod.share(_ctx(_trace("trace_small.textproto")), path) is None
    empty = xplane.Trace([[xplane.Event("x", 0, 1)]], [[]], [], (0, 1))
    assert mod.share(_ctx(empty), path) is None
    assert mod.share(_ctx(_trace("trace_small.textproto")), None) is None


def test_host_turnaround_ms(scoped):
    """host_sync ends at 10.2 and 25.1 ms, the next dispatches start at
    13.0 and 29.5 ms; the first dispatch (no sync before it) and the
    last sync (no dispatch after it) pair with nothing."""
    read = context.reader(os.path.join(CHIP, "metrics"),
                          "host_turnaround_ms")
    assert read(_ctx(scoped)) == pytest.approx((2.8 + 4.4) / 2)


def test_host_turnaround_ms_is_none_without_engine_spans(scoped):
    read = context.reader(os.path.join(CHIP, "metrics"),
                          "host_turnaround_ms")
    assert read(_ctx(_trace("trace_small.textproto"))) is None
    one = [e for e in scoped.host if e.name == "engine/host_sync"][:1]
    lone = xplane.Trace(scoped.ops, scoped.modules, one, scoped.span)
    assert read(_ctx(lone)) is None

