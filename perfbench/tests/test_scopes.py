"""The program's own names in a reduced trace: device time under a named
scope, device idle inside a host span, and the readers of the per-layer
metrics built on them, on hand-made events with hand-counted answers and
on the small trace recorded on a TPU v5e before the program named
anything (``data/tiny_v5e.xplane.pb``), where they read nothing."""
import gzip
import os
import types

import pytest

from perfbench import scopes, trace
from perfbench.metrics import traces_in_window, trajectory_idle_ms_per_chunk
from perfbench.trace import Device, Event, Summary

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny_v5e.xplane.pb")


def ev(name, start, end, **stats):
    return Event(name, start, end, stats)


@pytest.mark.parametrize("path,name,want", [
    ("jit(chunk)/while/body/repro.local_update/vmap(jvp())/dot",
     "repro.local_update", True),
    ("jit(chunk)/while/body/jvp(repro.local_update)/dot",
     "repro.local_update", True),
    ("jit(chunk)/vmap(jvp(repro.local_update))/conv", "repro.local_update",
     True),
    ("cond/branch_1_fun/repro.sync/repro.plane.ravel/concatenate",
     "repro.sync", True),
    ("cond/branch_1_fun/repro.sync.commit/select_n", "repro.sync", False),
    ("cond/branch_1_fun/repro.sync.commit/select_n", "repro.sync.commit",
     True),
    ("jit(chunk)/while/body/not_repro.sync/add", "repro.sync", False),
    ("", "repro.sync", False),
])
def test_in_scope(path, name, want):
    assert scopes.in_scope(path, name) is want


def _scoped_summary():
    # one chip, one chunk program (id 7) from 0 to 10 with five ops; the
    # window is 1-9
    modules = [ev("jit_chunk(7)", 0, 10)]
    ops = [ev("%fusion.1 = f32[] fusion()", 1, 3),        # local update
           ev("%convolution.2 = f32[] convolution()", 2, 4),
           ev("%fusion.3 = f32[] fusion()", 5, 6),        # ravel
           ev("%select.4 = f32[] select()", 6, 8),        # commit
           ev("%copy.5 = f32[] copy()", 8, 9.5)]          # no scope
    paths = {
        ("7", "fusion.1"): "jit(chunk)/while/body/repro.local_update/add",
        ("7", "convolution.2"):
            "jit(chunk)/while/body/vmap(jvp(repro.local_update))/conv",
        ("7", "fusion.3"):
            "cond/branch_1_fun/repro.sync/repro.plane.ravel/concatenate",
        ("7", "select.4"): "cond/branch_1_fun/repro.sync.commit/select_n",
        ("7", "copy.5"): "",
    }
    s = Summary([Device("/device:TPU:0", ops, modules)], [], (1, 9))
    return scopes.with_scopes(s, paths)


def test_scope_time_is_the_union_under_a_scope():
    s = _scoped_summary()
    # 1-3 and 2-4 overlap: 3 s under the local update
    assert scopes.scope_time(s, "repro.local_update") == pytest.approx(3)
    # the ravel only: the commit's op names repro.sync.commit alone
    assert scopes.scope_time(s, "repro.sync") == pytest.approx(1)
    assert scopes.scope_time(s, "repro.plane.ravel") == pytest.approx(1)
    assert scopes.scope_time(s, "repro.sync.commit") == pytest.approx(2)
    assert scopes.scope_time(s, "repro.plane.unravel") == 0
    # the unscoped copy is clipped to the window: 8-9
    assert s.op_time(lambda o: "scope" in o.stats) == pytest.approx(7)


def test_with_scopes_looks_ops_up_in_their_program():
    s = _scoped_summary()
    tagged = [o.stats.get("scope") for o in s.devices[0].ops]
    assert tagged[0].endswith("repro.local_update/add")
    # an op outside any program run, or of a program the table lacks,
    # carries no scope
    bare = Summary([Device("/device:TPU:0",
                           [ev("%fusion.1 = f32[] fusion()", 11, 12),
                            ev("%fusion.1 = f32[] fusion()", 1, 2)],
                           [ev("jit_fold(8)", 0, 10)])], [], (0, 20))
    s = scopes.with_scopes(bare, {("7", "fusion.1"): "repro.sync/add"})
    assert all("scope" not in o.stats for o in s.devices[0].ops)


def _host_summary():
    # device busy 0-1, 2-3, 5-6, 8-10; window 0.5-9.5; three trajectory
    # spans, the first straddling the window's start
    ops = [ev("%a = a", 0, 1), ev("%b = b", 2, 3), ev("%c = c", 5, 6),
           ev("%d = d", 8, 10)]
    host = [ev("repro.loop.trajectory", 0.2, 1.5, round=0),
            ev("repro.loop.trajectory", 2.5, 4.5, round=16),
            ev("repro.loop.trajectory", 6.5, 7.0, round=32),
            ev("repro.loop.chunk", 0.5, 9.0, round=16),
            ev("repro.compile", 3.0, 3.0, program="chunk", kind="traces"),
            ev("repro.compile", 9.8, 9.8, program="chunk", kind="compiles")]
    return Summary([Device("/device:TPU:0", ops, [])], host, (0.5, 9.5))


def test_idle_within_counts_only_spans_wholly_in_the_window():
    s = _host_summary()
    idle, n = scopes.idle_within(s, "repro.loop.trajectory")
    # 2.5-4.5 idles 3-4.5 (1.5 s); 6.5-7 idles wholly (0.5 s); the span
    # from 0.2 starts before the window and is left out
    assert n == 2
    assert idle == pytest.approx(2.0)
    assert scopes.idle_within(s, "repro.engine.fetch") == (0.0, 0)


def _run(summary):
    return types.SimpleNamespace(trace=summary, rounds=16)


def test_new_metrics_on_hand_made_events():
    s = _host_summary()
    assert trajectory_idle_ms_per_chunk.read(_run(s)) == pytest.approx(1e3)
    # one mark in the window; the one at 9.8 lies after it
    assert traces_in_window.read(_run(s)) == 1
    assert trajectory_idle_ms_per_chunk.read(_run(None)) is None
    assert traces_in_window.read(_run(None)) is None


def test_traces_in_window_reads_nothing_without_the_counter(monkeypatch):
    import repro.telemetry.trace as program_trace
    monkeypatch.delattr(program_trace, "COMPILE_MARK")
    assert traces_in_window.read(_run(_host_summary())) is None


@pytest.fixture(scope="module")
def recorded_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(FIXTURE + ".gz", "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_a_trace_from_before_the_names_reads_nothing(recorded_path):
    s = scopes.with_scopes(trace.reduce_file(recorded_path, chips=1),
                           scopes.op_scopes(recorded_path))
    # the ops carry their name paths, none of them the program's scopes
    paths = {o.stats.get("scope") for o in s.devices[0].ops}
    assert any(p and "conv_general_dilated" in p for p in paths)
    for name in ("repro.local_update", "repro.sync", "repro.plane.ravel",
                 "repro.plane.unravel"):
        assert scopes.scope_time(s, name) == 0
    assert trajectory_idle_ms_per_chunk.read(_run(s)) is None
    # the counter's reader asks the program in this process, which has
    # the counter, whether marks can exist: here it finds none
    assert traces_in_window.read(_run(s)) == 0
