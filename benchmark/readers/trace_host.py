"""Host time of the program's own spans inside the traced window: the
self time (``Trace.host_pieces``: every instant goes to the innermost
span that covers it) of the spans whose name starts with ``prefix``,
less those whose name ends with one of ``exclude`` (the spans in which
the host only waits for the device), over a counter of the driver's.
Parameters: ``prefix``, ``exclude``, ``counter``, ``scale``.

The full split is printed on an earlier line, so that a traced run's
output explains the number: every span with its self seconds, and the
share of ``bench.engine_step`` that the program's spans cover. A trace
without such spans (a program that has none) reads nothing and says so.
"""
from collections import defaultdict

from ..harness.session import say

OUTER = "bench.engine_step"


def self_seconds(trace) -> dict:
    """Self time of every host span, clipped to the window, in seconds."""
    acc = defaultdict(float)
    for lo, hi, name in trace.host_pieces:
        lo, hi = max(lo, trace.lo), min(hi, trace.hi)
        if hi > lo:
            acc[name] += (hi - lo) / 1e9
    return dict(acc)


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None:
        return None
    by = self_seconds(trace)
    prefix, exclude = params["prefix"], tuple(params.get("exclude", ()))
    own = {k: v for k, v in by.items() if k.startswith(prefix)}
    if not own:
        say(f"trace_host: NOTHING among the host spans starts with "
            f"{prefix!r}: the metric is left out")
        return None
    inside = sum(own.values())
    outer = by.get(OUTER, 0.0)
    split = sorted(by.items(), key=lambda kv: -kv[1])
    say(f"trace_host: self seconds by span "
        f"{ {k: round(v, 6) for k, v in split} }; {prefix}* cover "
        f"{100 * inside / (inside + outer):.2f}% of {OUTER}")
    den = ctx["counters"].get(params["counter"])
    if not den:
        return None
    busy = sum(v for k, v in own.items() if not k.endswith(exclude))
    return busy / den * params.get("scale", 1.0)
