"""The program's own names in a reduced trace (``trace.Summary``).

The program names its host work with profiler spans (``repro.loop.chunk``
and the spans inside it) and marks each trace or compile of a function
with a ``repro.compile`` host event; both are events of ``Summary.host``.
It names its device work with ``jax.named_scope`` (``repro.local_update``,
``repro.sync`` and its stages, ``repro.plane.ravel``/``unravel``): an op's
name path, which the TPU profiler's ``hlo_stats`` table gives as
``tf_op_name``. ``trace.reduce_file`` keeps only the op's category from
that table, so ``with_scopes`` reads the paths from the trace file and
puts each on its op event as stat ``scope``.
"""
from __future__ import annotations

import bisect
import json
from typing import Dict, Tuple

from perfbench import trace


def in_scope(path: str, name: str) -> bool:
    """True when a component of the name path ``path`` is ``name``, or is
    ``name`` inside transforms (``jvp(name)``, ``vmap(jvp(name))``). A
    component ``repro.sync.commit`` is not ``repro.sync``."""
    return any(c == name or c.rstrip(")").endswith("(" + name)
               for c in path.split("/"))


def op_scopes(path: str) -> Dict[Tuple[str, str], str]:
    """{(program id, HLO op name): name path} of the programs in the
    trace file, from ``xprof``'s ``hlo_stats`` table; empty where that
    tool is not installed."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError:
        return {}
    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    out = {}
    for row in table["rows"]:
        v = dict(zip(cols, (c.get("v") for c in row["c"])))
        out[(str(v["program_id"]), str(v["hlo_op_name"]))] = str(
            v.get("tf_op_name") or "")
    return out


def with_scopes(summary: trace.Summary, scopes) -> trace.Summary:
    """The summary with each op event's name path (``op_scopes``, looked
    up in the program whose run encloses the op) as stat ``scope``."""
    devices = []
    for d in summary.devices:
        mods = sorted(d.modules, key=lambda m: m.start)
        starts = [m.start for m in mods]
        ops = []
        for o in d.ops:
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.start <= mods[i].end:
                key = (mods[i].name.rsplit("(", 1)[-1].rstrip(")"),
                       trace.op_name(o.name))
                if key in scopes:
                    o = o._replace(stats=dict(o.stats, scope=scopes[key]))
            ops.append(o)
        devices.append(trace.Device(d.name, ops, d.modules))
    return trace.Summary(devices, summary.host, (summary.lo, summary.hi))


def scope_time(summary: trace.Summary, name: str) -> float:
    """Seconds of device ops under the named scope ``name``: the union of
    their intervals in the window, mean over chips."""
    return summary.op_time(
        lambda o: in_scope(str(o.stats.get("scope", "")), name))


def idle_within(summary: trace.Summary, span: str) -> Tuple[float, int]:
    """(device-idle seconds inside the host spans named ``span`` that lie
    wholly in the window, mean over chips; the number of such spans)."""
    spans = [h for h in summary.host if h.name == span
             and summary.lo <= h.start and h.end <= summary.hi]
    if not spans or not summary.devices:
        return 0.0, len(spans)
    idle = []
    for d in summary.devices:
        busy = trace.union(trace.clip([(o.start, o.end) for o in d.ops],
                                      summary.lo, summary.hi))
        idle.append(sum(trace.total(trace.gaps(busy, h.start, h.end))
                        for h in spans))
    return sum(idle) / len(idle), len(spans)
