"""The reduction from a profiler trace to per-layer numbers: on hand-made
events with hand-counted answers, and on a small trace recorded on a TPU
v5e (``data/tiny_v5e.xplane.pb``: the harness's tiny cell, m=8, traced
with ``--trace 1``)."""
import os

import pytest

from perfbench import trace
from perfbench.trace import Device, Event, Summary

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny_v5e.xplane.pb")


def ev(name, start, end):
    return Event(name, start, end, {})


def test_union_gaps_and_totals():
    u = trace.union([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert u == [(0, 3), (5, 7)]
    assert trace.total(u) == 5
    assert trace.gaps(u, -1, 8) == [(-1, 0), (3, 5), (7, 8)]
    assert trace.clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_self_times_subtract_nested_ops():
    ops = [ev("%while.1 = (...) while(...)", 0, 10), ev("%a = f32[] a()", 1, 3),
           ev("%b = f32[] b()", 4, 9), ev("%c = f32[] c()", 5, 6),
           ev("%d = f32[] d()", 11, 12)]
    got = dict(trace.self_times(ops, 0, 20))
    assert got == {"while.1": 3, "a": 2, "b": 4, "c": 1, "d": 1}


def _summary():
    # one chip: sampler 0-1, chunk 1-5 (ops inside), fold 5-5.5, host
    # bookkeeping, then sampler 6-7, chunk 7-9, fold 9-9.5
    modules = [ev("jit_sample_chunk(1)", 0, 1), ev("jit_chunk(2)", 1, 5),
               ev("jit_fold(3)", 5, 5.5), ev("jit__reduce_sum(4)", 5.7, 5.8),
               ev("jit_sample_chunk(1)", 6, 7), ev("jit_chunk(2)", 7, 9),
               ev("jit_fold(3)", 9, 9.5)]
    ops = [ev("%while.4 = s", 0, 1), ev("%while.9 = c", 1, 5),
           ev("%convolution.3 = f32[] convolution()", 1.5, 2.5),
           ev("%sqdist_rows.7 = f32[] custom-call()", 3, 3.5),
           ev("%fusion.2 = f32[] fusion(%sqdist_rows.7)", 3.5, 3.6),
           ev("%fold.1 = f", 5, 5.5), ev("%reduce.1 = r", 5.7, 5.8),
           ev("%while.4 = s", 6, 7), ev("%while.9 = c", 7, 9),
           ev("%sqdist_rows.7 = f32[] custom-call()", 8, 8.5),
           ev("%fold.1 = f", 9, 9.5)]
    host = [ev("CommonPjRtBuffer::ToLiteral", 5.4, 5.85),
            ev("$loop.py:131 run_protocol_training", 5.5, 6.2)]
    return Summary([Device("/device:TPU:0", ops, modules)], host, (0, 10))


def test_summary_shares_and_gaps():
    s = _summary()
    assert s.window_s == 10
    assert s.busy_s == pytest.approx(1 + 4 + 0.5 + 0.1 + 1 + 2 + 0.5)
    assert s.idle_share() == pytest.approx(1 - 9.1 / 10)
    assert s.module_time("jit_sample_chunk") == pytest.approx(2)
    # from the end of the fold at 5.5 to the sampler at 6: 0.5 s, of
    # which 0.1 s busy with the loop's reduce -> 0.4 s idle
    assert s.idle_between("jit_fold", "jit_sample_chunk") == [
        pytest.approx(0.4)]


def test_summary_kernel_calls_and_breakdown():
    s = _summary()

    def is_kernel(op):
        return trace.op_name(op.name).split(".")[0] == "sqdist_rows"

    assert s.op_calls(is_kernel) == 2          # not the fusion that reads it
    assert s.op_time(is_kernel) == pytest.approx(1.0)
    top = dict(s.top_ops())
    assert top["while.9"] == pytest.approx(4 + 2 - 1 - 0.5 - 0.1 - 0.5)
    # idle: 5.5-5.7 (transfer and loop both cover it wholly: the shorter,
    # inner one names it), 5.8-6.0 (the transfer covers a quarter, the
    # loop all of it), 9.5-10 (nothing)
    gaps = s.idle_gaps()
    assert [n for n, _ in gaps] == ["idle", "CommonPjRtBuffer::ToLiteral",
                                    "$loop.py:131 run_protocol_training"]
    assert [g for _, g in gaps] == pytest.approx([0.5, 0.2, 0.2])


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The recorded trace, unpacked where the profiler's tools can read
    it by path."""
    import gzip
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(FIXTURE + ".gz", "rb") as f:
        path.write_bytes(f.read())
    return trace.reduce_file(str(path), chips=1)


def test_recorded_trace_programs(recorded):
    s = recorded
    names = {trace.module_name(m.name) for m in s.devices[0].modules}
    assert {"jit_sample_chunk", "jit_chunk", "jit_fold"} <= names
    assert s.window_s == pytest.approx(0.3055, abs=1e-3)
    assert 0 < s.busy_s < s.window_s
    # the tiny fleet keeps the chip idle nearly all the time
    assert 0.9 < s.idle_share() < 1.0
    busy_programs = sum(s.module_time(n) for n in names)
    assert s.busy_s <= busy_programs * (1 + 1e-6)


def test_recorded_trace_chunk_boundaries(recorded):
    s = recorded
    chunks = sum(1 for m in s.devices[0].modules
                 if trace.module_name(m.name) == "jit_chunk"
                 and s.lo <= m.start and m.end <= s.hi)
    idle = s.idle_between("jit_fold", "jit_sample_chunk")
    assert len(idle) == chunks - 1
    assert all(0 < g < 0.05 for g in idle)


def test_recorded_trace_categories(recorded):
    s = recorded
    cats = {o.stats.get("hlo_category") for o in s.devices[0].ops}
    assert {"convolution fusion", "loop fusion", "data formatting"} <= cats
    conv = s.op_time(lambda o: "convolution" in o.stats.get(
        "hlo_category", ""))
    assert 0 < conv < s.module_time("jit_chunk")
    # P = 3,722 columns is less than one kernel block: sqdist_rows
    # reduces the whole row as its tail and launches no kernel
    assert s.op_calls(lambda o: trace.op_name(o.name).startswith(
        "sqdist_rows")) == 0
