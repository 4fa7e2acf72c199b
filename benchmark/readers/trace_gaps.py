"""The device's idle time inside the spans in which the host only waits
(the host pieces whose name ends in ``suffix``: ``.fetch``), split in
two by what ended each gap.

A gap of the device (``Trace.busy`` / ``complement``, as
``Trace.idle_by_host_span`` takes them) is ``queued`` when the instant
it ENDS lies inside such a piece: the device resumed by itself while the
host still waited, so the work was queued and had not started (the time
between two programs of one queue). It is ``return`` otherwise: the
device resumed only after the host had come back and done something, so
everything queued was finished and the host had not heard (a download
and a thread's wake-up). Either way only the gap's seconds inside such
pieces are counted, whichever piece they fall in, so the two kinds sum
to the ``*.fetch`` rows of the result's ``idle_gaps``; the reader holds
that to a microsecond and prints it.

Parameters: ``suffix``, ``kind`` (``queued`` or ``return``),
``counter``, ``scale``: the kind's seconds over a counter of the
driver's. A kind with no gap reads 0.0, not nothing: the split was made
and found none.

The same reader prints one **closure line** a traced run: the wall a
traced decode step (the slice over the counter) as the device time of
the modules matching ``closure.decode``, of those matching
``closure.prefill``, of every other module, and the idle time, each in
ms a step, and what is left: a share nobody named shows as a residue.
"""
from __future__ import annotations

import bisect

from ..harness.session import say
from ..harness.trace_reduce import MODULES, complement, total

KINDS = ("queued", "return")
_SAID: set = set()      # the traces whose lines are printed already


def split(trace, suffix: str, plane: str = None) -> dict:
    """``{"queued": s, "return": s}``: idle seconds inside the pieces
    whose name ends in ``suffix``, a gap at a time; and under
    ``queued.in_program`` the part of ``queued`` whose gap lies inside
    one program's event (between two of its operations, not between two
    programs), for the printed line."""
    plane = plane or trace.planes[0]
    pieces = [(lo, hi) for lo, hi, name in trace.host_pieces
              if name.endswith(suffix)]
    starts = [lo for lo, _ in pieces]
    mods = sorted((m.start, m.end) for m in trace.on(plane, MODULES))
    mod_starts = [lo for lo, _ in mods]
    out = dict.fromkeys(KINDS + ("queued.in_program",), 0.0)
    for s, e in complement(trace.busy(plane), trace.lo, trace.hi):
        inside, resumed_alone = 0.0, False
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(pieces) and pieces[i][0] < e:
            lo, hi = pieces[i]
            inside += max(0.0, min(e, hi) - max(s, lo))
            resumed_alone = resumed_alone or lo < e < hi
            i += 1
        if not inside:
            continue
        out["queued" if resumed_alone else "return"] += inside / 1e9
        j = bisect.bisect_right(mod_starts, s) - 1
        if resumed_alone and j >= 0 and e <= mods[j][1]:
            out["queued.in_program"] += inside / 1e9
    return out


def closure(trace, patterns: dict, steps: float, plane: str = None) -> dict:
    """ms a traced step: the wall, its parts, and the residue."""
    per = 1e3 / steps
    parts = {k: trace.matching(MODULES, p, plane)[0] * per
             for k, p in patterns.items()}
    parts["other"] = trace.matching(MODULES, "", plane)[0] * per \
        - sum(parts.values())
    parts["idle"] = (trace.window_s
                     - total(trace.busy(plane or trace.planes[0])) / 1e9) * per
    wall = trace.window_s * per
    return {"wall": wall, **parts, "residue": wall - sum(parts.values())}


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None or not trace.planes:
        return None
    steps = ctx["counters"].get(params["counter"])
    if not steps:
        return None
    suffix = params["suffix"]
    got = split(trace, suffix)
    rows = sum(v for k, v in trace.idle_by_host_span().items()
               if k.endswith(suffix))
    both = got["queued"] + got["return"]
    assert abs(both - rows) < 1e-6, (got, rows)
    if id(trace) not in _SAID:
        _SAID.add(id(trace))
        say(f"trace_gaps: idle inside *{suffix} spans "
            f"{ {k: round(v, 6) for k, v in got.items()} } s, queued and "
            f"return together {both:.6f} = the idle_gaps rows' {rows:.6f}; "
            f"over {steps:g} steps")
        if "closure" in params:
            c = closure(trace, params["closure"], steps)
            say("trace_gaps: closure, ms a traced step: " + ", ".join(
                f"{k} {v:.4f}" for k, v in c.items())
                + f" ({100 * c['residue'] / c['wall']:.2f}% of the wall)")
    return got[params["kind"]] / steps * params.get("scale", 1.0)
