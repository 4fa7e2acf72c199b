"""Device time of one program's operations by the ``jax.named_scope``
they were traced under (``embed``, ``attn.proj``, ``attn.kernel``, ...:
``SCOPES``, the program's own list, ``docs/observability.md``).

An op's scope is in its ``op_name`` (``jit(step)/transpose(jvp(ce))/ce/
while/body/...``), which the event's name does not hold and
``trace_reduce.load_xplane`` drops: this reader takes it from the
statistics the profiler attaches to each op event, reading the run's
``.xplane.pb`` (the newest under ``session.SCRATCH/trace/``) itself. A
scope counts as a component anywhere in the name, innermost first:
backward ops read ``transpose(jvp(mlp))/...``, scan bodies
``while/body/...``, and a recomputed forward passes through
``rematted_computation``. A fusion carries one of its ops' names; ops of
XLA's own making (copies, a scan's slices) carry none and are
``(unscoped)``. Time is self time (``trace_reduce.flatten``: a loop's own
event does not count its body twice), inside the events of the program
on the modules line.

Parameters: ``program`` (pattern of the module), ``sum`` (the scopes, or
``(unscoped)``, to add up), optionally ``through`` (count only ops whose
name passes through this component, whatever their scope), and ``per``:
``events`` (over the program's whole events in the slice, per event) or
``counter`` (all of them, clipped to the window, over a driver's
counter). The full split is printed on an earlier line. Where the trace
holds no op name at all, or the program has no scopes, the reader says
so and reads nothing.
"""
from __future__ import annotations

import functools
import os
import re
from collections import defaultdict

from ..harness import session
from ..harness.session import say
from ..harness.trace_reduce import MODULES, OPS, flatten, is_device

SCOPES = ("embed", "attn.proj", "attn.kernel", "attn.kv_write", "mlp",
          "moe.route", "moe.dispatch", "moe.experts", "moe.combine",
          "moe.shared", "head", "ce", "optim")
UNSCOPED = "(unscoped)"
# the statistic of an op's metadata that holds its op_name: ``tf_op`` on
# the v5e (PERF.md §3), ``hlo_op`` names the instruction elsewhere
NAME_STATS = ("tf_op",)
_SPLIT = re.compile(r"[/();:]")
_SAID: set = set()      # the splits already printed in this process


def newest_xplane(root: str = None):
    root = root or os.path.join(session.SCRATCH, "trace")
    found = [os.path.join(base, f) for base, _, files in os.walk(root)
             for f in files if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


# -- the .xplane.pb, read as protobuf wire format -------------------------
# The op's name is a statistic of the event's METADATA (``tf_op``, one
# record an instruction), which ``jax.profiler.ProfileData`` does not
# show: its ``ev.stats`` are the event's own (offset and duration on the
# v5e). So the file is read here, by field number (xplane.proto: XSpace
# planes=1; XPlane name=2 lines=3 event_metadata=4 stat_metadata=5; XLine
# name=2 timestamp_ns=3 events=4; XEvent metadata_id=1 offset_ps=2
# duration_ps=3; XEventMetadata name=2 stats=5; XStat metadata_id=1
# str_value=5 ref_value=7; XStatMetadata name=2; a map entry key=1
# value=2). Fields of other wire types are stepped over, the programs'
# HLO on ``/host:metadata`` among them.

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            elif kind in (1, 5):
                size = 8 if kind == 1 else 4
            else:
                raise ValueError(f"wire type {kind} in an xplane")
            val, i = buf[i:i + size], i + size
        yield key >> 3, val


def _message(buf, strings=(), ints=()) -> dict:
    """The named fields of one message (the last of each), as str or int;
    ``{number: name}`` for both kinds."""
    out = {}
    for no, val in _fields(buf):
        if no in strings and not isinstance(val, int):
            out[strings[no]] = bytes(val).decode("utf-8", "replace")
        elif no in ints and isinstance(val, int):
            out[ints[no]] = val
    return out


def _map(entries, strings):
    """``{key: named string fields}`` of a map's entries."""
    out = {}
    for buf in entries:
        key, value = 0, b""
        for no, val in _fields(buf):
            if no == 1:
                key = val
            elif no == 2:
                value = val
        out[key] = (_message(value, strings), value)
    return out


def _plane_ops(buf) -> list:
    """``[name, start_ns, dur_ns, op_name]`` of the ops line's events of
    one plane, and the plane's name."""
    name, lines, emeta, smeta = "", [], [], []
    for no, val in _fields(buf):
        if no == 2:
            name = bytes(val).decode()
        elif no == 3:
            lines.append(val)
        elif no == 4:
            emeta.append(val)
        elif no == 5:
            smeta.append(val)
    if not is_device(name):
        return name, []
    stat_name = {k: m.get("name", "")
                 for k, (m, _) in _map(smeta, {2: "name"}).items()}
    meta = {}
    for k, (m, raw) in _map(emeta, {2: "name"}).items():
        op_name = ""
        for no, val in _fields(raw):
            if no != 5:
                continue
            stat = _message(val, {5: "str"}, {1: "id", 7: "ref"})
            if stat_name.get(stat.get("id")) in NAME_STATS:
                op_name = stat.get("str") or stat_name.get(stat.get("ref"),
                                                           "")
        meta[k] = (m.get("name", ""), op_name)
    out = []
    for line in lines:
        head = _message(line, {2: "name"}, {3: "t0"})
        if head.get("name") != OPS:
            continue
        for no, val in _fields(line):
            if no != 4:
                continue
            ev = _message(val, ints={1: "meta", 2: "offset", 3: "dur"})
            ev_name, op_name = meta.get(ev.get("meta"), ("", ""))
            out.append([ev_name,
                        head.get("t0", 0) + ev.get("offset", 0) / 1e3,
                        ev.get("dur", 0) / 1e3, op_name])
    return name, out


@functools.lru_cache(maxsize=1)
def load_ops(path: str) -> list:
    """``[plane, name, start_ns, dur_ns, op_name]`` of every event on a
    device's ops line (``op_name`` ``""`` where the metadata holds none),
    on the clock of ``trace_reduce.load_xplane``'s events. Kept for the
    run's other metrics of this reader: one file a run."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for no, val in _fields(space):
        if no == 1:
            plane, ops = _plane_ops(val)
            out += [[plane] + o for o in ops]
    return out


def components(op_name: str) -> list:
    return [c for c in _SPLIT.split(op_name) if c]


def scope_of(op_name: str) -> str:
    """The innermost named scope in an op's name."""
    for c in reversed(components(op_name)):
        if c in SCOPES:
            return c
    return UNSCOPED


def split(trace, ops: list, params: dict, plane: str = None) -> dict:
    """``{"by": {scope: seconds}, "through": seconds, "events": n,
    "top_unscoped": [[name, seconds]]}`` of the program's ops on one
    device, as the parameters select events."""
    plane = plane or trace.planes[0]
    rx = re.compile(params["program"])
    mods = [e for e in trace.on(plane, MODULES) if rx.search(e.name)]
    if params.get("per") == "events":
        mods = [e for e in mods if trace.whole(e)]
    spans = sorted((max(e.start, trace.lo), min(e.end, trace.hi))
                   for e in mods)
    mine = [o for o in ops if o[0] == plane]
    by, loose = defaultdict(float), defaultdict(float)
    through, want = 0.0, params.get("through")
    i = 0
    for lo, hi, k in flatten((o[2], o[2] + o[3], k)
                             for k, o in enumerate(mine)):
        # (pieces come sorted by start, and so are the program's events)
        while i < len(spans) and spans[i][1] <= lo:
            i += 1
        j, secs = i, 0.0
        while j < len(spans) and spans[j][0] < hi:
            secs += max(0.0, min(hi, spans[j][1]) - max(lo, spans[j][0]))
            j += 1
        if not secs:
            continue
        name = mine[k][4]
        scope = scope_of(name)
        by[scope] += secs / 1e9
        if scope == UNSCOPED:
            loose[mine[k][1].split(" = ")[0] + " " + name[-60:]] \
                += secs / 1e9
        if want and want in components(name):
            through += secs / 1e9
    return {"by": dict(by), "through": through, "events": len(mods),
            "named": sum(1 for o in mine if o[4]),
            "top_unscoped": sorted(loose.items(), key=lambda kv: -kv[1])[:8]}


def _say_once(key, msg: str) -> None:
    """A split is said once a run, not once for each metric it feeds."""
    if key not in _SAID:
        _SAID.add(key)
        say(msg)


def read(params: dict, ctx: dict, ops: list = None):
    trace = ctx["trace"]
    if trace is None or not trace.planes:
        return None
    if ops is None:
        path = newest_xplane()
        if path is None:
            return None
        ops = load_ops(path)
    got = split(trace, ops, params)
    scoped = {k: v for k, v in got["by"].items() if k != UNSCOPED}
    said = (id(trace), params["program"], params.get("per"),
            params.get("through"))
    if not got["events"] or not got["named"] or not scoped:
        _say_once(said[:2], (
            f"trace_scope: NOTHING to split for {params['program']!r} "
            f"({got['events']} events of the program, {got['named']} ops "
            f"with a name, {len(scoped)} scopes found): its metrics are "
            f"left out"))
        return None
    den = got["events"] if params.get("per") == "events" \
        else ctx["counters"].get(params["counter"])
    if not den:
        return None
    by = sorted(got["by"].items(), key=lambda kv: -kv[1])
    _say_once(said, (
        f"trace_scope: {params['program']!r}, {got['events']} events, "
        f"seconds by scope { {k: round(v, 6) for k, v in by} }, sum "
        f"{sum(got['by'].values()):.6f}; through "
        f"{params.get('through')!r} {got['through']:.6f}; over {den:g}; "
        f"most of {UNSCOPED}: "
        f"{[[k, round(v, 6)] for k, v in got['top_unscoped']]}"))
    if "through" in params:
        secs = got["through"]
    else:
        secs = sum(got["by"].get(s, 0.0) for s in params["sum"])
    return secs / den * params.get("scale", 1.0)
