"""From a profiler trace to numbers. The reduction works on a plain list
of events ``(plane, line, name, start_ns, dur_ns)``, so that it can be
checked on hand-written events with known answers; ``load_xplane`` makes
that list from the ``.xplane.pb`` the JAX profiler writes.

Device planes are those named ``/device:TPU:<n>``. On each, the line
``XLA Ops`` holds one event an executed operation (operations of a loop
nest inside the loop's own event) and ``XLA Modules`` one event a
launched program. Host spans are the benchmark's own
``jax.profiler.TraceAnnotation``s (``bench.*``) and whatever spans of the
program (``serving.*``) happen to be in the trace.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import NamedTuple

OPS, MODULES = "XLA Ops", "XLA Modules"
HOST_PREFIXES = ("bench.", "serving.")
WINDOW_SPAN = "bench.trace_window"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start: float      # ns
    dur: float        # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def is_device(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def load_xplane(path: str) -> list:
    """Device events of the ops and modules lines, and host spans with
    the prefixes above, as ``Event``s on the profiler's one clock."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = is_device(plane.name)
        for line in plane.lines:
            if dev and line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                if dev or ev.name.startswith(HOST_PREFIXES):
                    out.append(Event(plane.name, line.name, ev.name,
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


# -- intervals -----------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def complement(merged, lo: float, hi: float) -> list:
    out, at = [], lo
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def flatten(spans) -> list:
    """Properly nested ``(start, end, name)`` spans to disjoint
    ``(start, end, name)`` pieces, each named by the innermost span that
    covers it: a span's self time is the sum of its pieces."""
    points = []
    for s, e, n in spans:
        if e > s:
            points.append((s, 1, s - e, n))
            points.append((e, 0, 0.0, n))
    points.sort(key=lambda p: (p[0], p[1], p[2]))
    stack, out, last = [], [], None
    for t, opens, _, name in points:
        if stack and t > last:
            out.append((last, t, stack[-1]))
        if opens:
            stack.append(name)
        else:
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] == name:
                    del stack[i]
                    break
        last = t
    return out


def overlap_by_name(gaps, pieces) -> dict:
    """Seconds of ``gaps`` under each name of disjoint sorted ``pieces``;
    what no piece covers goes to ``(no span)``."""
    out = defaultdict(float)
    starts = [p[0] for p in pieces]
    for s, e in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(pieces) and pieces[i][0] < e:
            lo, hi = max(s, pieces[i][0]), min(e, pieces[i][1])
            if hi > lo:
                out[pieces[i][2]] += hi - lo
                covered += hi - lo
            i += 1
        if e - s > covered:
            out["(no span)"] += e - s - covered
    return dict(out)


# -- the reduction ------------------------------------------------------

class Trace:
    """One traced slice. Times come back in seconds."""

    def __init__(self, events):
        self.events = [Event(*e) for e in events]
        self.planes = sorted({e.plane for e in self.events
                              if is_device(e.plane)},
                             key=lambda p: int(p.rsplit(":", 1)[1]))
        host = [e for e in self.events if not is_device(e.plane)]
        win = [e for e in host if e.name == WINDOW_SPAN]
        dev = [e for e in self.events if is_device(e.plane)]
        if win:
            self.lo, self.hi = win[0].start, win[0].end
        elif dev:
            self.lo = min(e.start for e in dev)
            self.hi = max(e.end for e in dev)
        else:
            self.lo = self.hi = 0.0
        self.host_pieces = flatten(
            (e.start, e.end, e.name) for e in host if e.name != WINDOW_SPAN)
        self._on, self._self_time, self._extent = {}, {}, {}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def on(self, plane: str, line: str) -> list:
        """The events of one line that touch the window."""
        if (plane, line) not in self._on:
            self._on[plane, line] = [
                e for e in self.events if e.plane == plane and e.line == line
                and e.end > self.lo and e.start < self.hi]
        return self._on[plane, line]

    def self_time(self, plane: str) -> list:
        """The ops line as disjoint ``(start, end, event)`` pieces clipped
        to the window, each given to its innermost operation."""
        if plane not in self._self_time:
            evs = self.on(plane, OPS)
            self._self_time[plane] = [
                (max(lo, self.lo), min(hi, self.hi), evs[i])
                for lo, hi, i in flatten((e.start, e.end, i)
                                         for i, e in enumerate(evs))
                if min(hi, self.hi) > max(lo, self.lo)]
        return self._self_time[plane]

    def whole(self, e: Event) -> bool:
        """Whether the trace holds all of this device event. The profiler
        records an operation that was running when it started from that
        instant on, and one running when it stopped up to that instant,
        so an event that touches the first or the last instant of its
        device's events may be shorter than the operation was (where the
        host runs ahead of the device, as in training, it always is)."""
        if e.plane not in self._extent:
            dev = [x for x in self.events if x.plane == e.plane]
            self._extent[e.plane] = (min(x.start for x in dev),
                                     max(x.end for x in dev))
        first, last = self._extent[e.plane]
        return (e.start > max(first, self.lo)
                and e.end < min(last, self.hi))

    def busy(self, plane: str) -> list:
        return union(clip(((e.start, e.end) for e in self.on(plane, OPS)),
                          self.lo, self.hi))

    def busy_s(self) -> float:
        """Seconds an operation ran, averaged over the devices."""
        if not self.planes:
            return 0.0
        return sum(total(self.busy(p)) for p in self.planes) \
            / len(self.planes) / 1e9

    def idle_share(self) -> float:
        """1 - busy / window, on the device that idled most."""
        if not self.planes or self.hi <= self.lo:
            return None
        return max(1 - total(self.busy(p)) / (self.hi - self.lo)
                   for p in self.planes)

    def idle_by_host_span(self, plane: str = None) -> dict:
        """The device's idle seconds by what the host was doing."""
        plane = plane or self.planes[0]
        gaps = complement(self.busy(plane), self.lo, self.hi)
        return {k: v / 1e9
                for k, v in overlap_by_name(gaps, self.host_pieces).items()}

    def matching(self, line: str, pattern: str, plane: str = None,
                 whole: bool = False) -> tuple:
        """(seconds, events) of the events on ``line`` whose name matches
        ``pattern``, both of the same set. Seconds are clipped to the
        window (on the ops line the self time: a loop's own event does
        not count its body twice), and an event the window's edge cuts
        counts as the share of it that lies inside. With ``whole`` only
        events the trace holds all of are summed and counted (see
        ``whole``), so that seconds over events is an operation's length
        wherever the slice fell, and work a call times events is the work
        those seconds did."""
        plane = plane or self.planes[0]
        rx = re.compile(pattern)
        evs = [e for e in self.on(plane, line) if rx.search(e.name)
               and e.dur > 0 and (not whole or self.whole(e))]
        n = sum((min(e.end, self.hi) - max(e.start, self.lo)) / e.dur
                for e in evs)
        if line == OPS:
            keep = set(evs)
            secs = sum(hi - lo for lo, hi, e in self.self_time(plane)
                       if e in keep)
        else:
            secs = total(clip(((e.start, e.end) for e in evs),
                              self.lo, self.hi))
        return secs / 1e9, n

    def top(self, line: str, n: int = 10, plane: str = None) -> list:
        """The ``n`` names with most device time on ``line`` (self time
        on the ops line), as ``[name, seconds]``."""
        plane = plane or self.planes[0]
        acc = defaultdict(float)
        if line == OPS:
            for lo, hi, e in self.self_time(plane):
                acc[e.name] += hi - lo
        else:
            for e in self.on(plane, line):
                acc[e.name] += max(0.0, min(e.end, self.hi)
                                   - max(e.start, self.lo))
        return [[k, v / 1e9] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def breakdown(self) -> dict:
        """The ledger's trace: operations by device time (programs first,
        then operations), and idle time by host span."""
        if not self.planes:
            return {"device_ops": [], "idle_gaps": []}
        mods = [[f"module {k}", v] for k, v in self.top(MODULES, 3)]
        # an op's name is its whole HLO text: its head says what it is
        ops = [[k[:160], v] for k, v in self.top(OPS, 10 - len(mods))]
        idle = sorted(self.idle_by_host_span().items(),
                      key=lambda kv: -kv[1])[:10]
        return {"device_ops": mods + ops,
                "idle_gaps": [[k, v] for k, v in idle]}

    def reduced(self, keep: int = None) -> list:
        """The events as plain lists (for ``testdata``)."""
        evs = self.events if keep is None else self.events[:keep]
        return [[e.plane, e.line, e.name, e.start, e.dur] for e in evs]


def dump(xplane: str, trace: Trace, out_dir: str, tag: str) -> None:
    """What a trace holds, to look at by hand before writing a pattern
    against it: every plane and line with its event count and the names
    that took most time, and the reduced events as gzipped JSON."""
    import gzip
    import json
    import os

    from jax.profiler import ProfileData

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{tag}.trace.txt"), "w") as f:
        for plane in ProfileData.from_file(xplane).planes:
            f.write(f"PLANE {plane.name}\n")
            for line in plane.lines:
                acc, n = defaultdict(lambda: [0.0, 0]), 0
                for ev in line.events:
                    acc[ev.name][0] += ev.duration_ns
                    acc[ev.name][1] += 1
                    n += 1
                f.write(f"  LINE {line.name!r}: {n} events, "
                        f"{len(acc)} names\n")
                for name, (ns, k) in sorted(acc.items(),
                                            key=lambda kv: -kv[1][0])[:40]:
                    f.write(f"    {ns / 1e6:12.3f} ms {k:7d} x {name[:160]}\n")
    with gzip.open(os.path.join(out_dir, f"{tag}.events.json.gz"), "wt") as f:
        json.dump(trace.reduced(), f)
