"""What the program's spans say they launched, over the device time of
the programs they launched. A span that dispatches a program carries the
work of that one call as keyword attrs (``serving.prefill.dispatch``:
``rows``, ``width``, ``tokens``), which reach the ``.xplane.pb`` as the
host event's statistics; ``trace_reduce.load_xplane`` keeps no
statistics, so this reader opens the run's file itself (the newest under
``session.SCRATCH/trace/``, as ``trace_scope`` does).

Spans and programs are paired **first in, first out**: the engine is one
thread and the device runs programs in the order they were dispatched,
so the modules matching ``program``, in start order, each take the
earliest span named ``span`` not yet taken that began before the module
began. A module with no such span (its dispatch preceded the slice) and
a span no module took (the slice ended first) are left out, and so is a
pair whose module the trace does not hold whole (``Trace.whole``): the
attr's sum and the seconds are of the same calls.

"Before" is judged across two clocks. The profiler lays the device's
timeline on the host's with an error of its own: in the first traced
process on a fresh machine a prefill module reads as starting 1.1 ms
BEFORE its own dispatch span begins (in later processes 0.2 ms after
it; PERF.md section 6, PR 35), and the bare rule then takes every
module for the one dispatched before it. ``slack_ms`` is how far a
module may begin before its span by that error; the printed line gives
the smallest lag found, and for every shape the number of distinct
programs it was paired with, which is 1 where the pairing is right (a
shape is one compiled program).

Parameters: ``span``, ``attr``, ``program`` (pattern on the modules
line), optionally ``slack_ms`` and ``scale``. Reports sum(attr) /
device seconds of the pairs. The pairs' count, both sums and the split
by shape (``rows`` x ``width``: calls, seconds, the attr's sum, distinct
programs) are printed on an earlier line. Where no span of that name
carries the attr (a program that says nothing there) the reader says so
and reads nothing.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict

from ..harness.session import say
from ..harness.trace_reduce import HOST_PREFIXES, MODULES, is_device
from .trace_scope import newest_xplane


@functools.lru_cache(maxsize=1)
def load_spans(path: str) -> list:
    """``(name, start_ns, dur_ns, {attr: value})`` of every host span
    with the harness's prefixes, on ``load_xplane``'s clock."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if is_device(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIXES):
                    out.append((ev.name, float(ev.start_ns),
                                float(ev.duration_ns), dict(ev.stats)))
    return out


def pair(trace, spans: list, params: dict, plane: str = None) -> list:
    """``[(span, module)]``, first in, first out, whole modules only."""
    plane = plane or trace.planes[0]
    rx = re.compile(params["program"])
    slack = params.get("slack_ms", 0.0) * 1e6
    mods = sorted((e for e in trace.on(plane, MODULES) if rx.search(e.name)),
                  key=lambda e: e.start)
    mine = sorted((s for s in spans if s[0] == params["span"]),
                  key=lambda s: s[1])
    out, i = [], 0
    for m in mods:
        if i < len(mine) and mine[i][1] < m.start + slack:
            if trace.whole(m):
                out.append((mine[i], m))
            i += 1
    return out


def read(params: dict, ctx: dict, spans: list = None):
    trace = ctx["trace"]
    if trace is None or not trace.planes:
        return None
    if spans is None:
        path = newest_xplane()
        if path is None:
            return None
        spans = load_spans(path)
    name, attr = params["span"], params["attr"]
    if not any(s[0] == name and attr in s[3] for s in spans):
        say(f"trace_spans: NOTHING among the {name!r} spans "
            f"({sum(s[0] == name for s in spans)} of them) carries "
            f"{attr!r}: the metric is left out")
        return None
    pairs = pair(trace, spans, params)
    by = defaultdict(lambda: [0, 0.0, 0.0, set()])
    for s, m in pairs:
        cell = by[f"{s[3].get('rows', '?')}x{s[3].get('width', '?')}"]
        cell[0] += 1
        cell[1] += m.dur / 1e9
        cell[2] += float(s[3].get(attr, 0))
        cell[3].add(m.name)
    secs = sum(cell[1] for cell in by.values())
    work = sum(cell[2] for cell in by.values())
    shapes = {k: [n, round(t, 6), w, len(names)] for k, (n, t, w, names)
              in sorted(by.items(), key=lambda kv: -kv[1][1])}
    lag = min((m.start - s[1] for s, m in pairs), default=0.0) / 1e6
    say(f"trace_spans: {len(pairs)} pairs of {name!r} and "
        f"{params['program']!r} (first in, first out; whole modules; a "
        f"module begins {lag:.3f} ms after its span at the least), "
        f"{attr} {work:g} over {secs:.6f} s; by rows x width "
        f"[calls, seconds, {attr}, programs]: {shapes}")
    if not secs:
        return None
    return work / secs * params.get("scale", 1.0)
