"""Device time of the trace's events on one line whose name matches a
pattern. Parameters: ``line`` (``XLA Ops`` or ``XLA Modules``),
``pattern``, and what to report:

- ``per: window`` and ``scale``: that time over the traced window;
- ``per: events``: the mean length of those events, over the ones the
  trace holds all of (one that was running when the profiler started or
  stopped is cut short by it); ``per: counter``: that time over a counter
  of the driver's, where the driver waits for each call, so that its
  count and the trace's events are the same calls;
- ``roofline``: the least time the chip could take for the work the calls
  had to do (a named function of ``harness/work.py``, FLOPs over the
  peak FLOP/s or bytes over the peak bytes/s) over their time. Work
  counted from the trace's own calls (``flash_flops``) takes whole events
  only, calls and seconds alike.

A pattern that matches nothing reads nothing, and says so on an earlier
line of the output: the program's names have changed under the metric.
"""
from ..harness import work
from ..harness.session import say


def _flash(params, ctx, trace):
    """Share of peak of the whole flash calls in the trace."""
    line = params["line"]
    calls = {k: trace.matching(line, pat, whole=True)[1]
             for k, pat in params["roofline"]["calls"].items()}
    flops = work.flash_flops(calls, **work.flash_shape(ctx["config"],
                                                       ctx["mix"]))
    secs = trace.matching(line, params["pattern"], whole=True)[0]
    return flops / ctx["peaks"]["flops"], secs


def _paged(params, ctx, trace):
    """The driver's count of live tokens at each step it waited for,
    against every call's time."""
    byts = work.paged_attn_bytes(ctx["counters"]["kv_token_steps"],
                                 ctx["config"])
    secs = trace.matching(params["line"], params["pattern"])[0]
    return byts / ctx["peaks"]["hbm_bytes"], secs


LEAST_TIME = {"flash_flops": _flash, "paged_attn_bytes": _paged}


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if trace is None or not trace.planes:
        return None
    secs, n = trace.matching(params["line"], params["pattern"])
    if not n or not secs:
        say(f"trace_ops: NOTHING on {params['line']!r} matches "
            f"{params['pattern']!r}: the metric is left out")
        return None
    if "roofline" in params:
        least, secs = LEAST_TIME[params["roofline"]["work"]](params, ctx,
                                                             trace)
        return 100.0 * least / secs if secs else None
    per = params.get("per", "window")
    if per == "events":
        secs, den = trace.matching(params["line"], params["pattern"],
                                   whole=True)
    else:
        den = trace.window_s if per == "window" \
            else ctx["counters"].get(params["counter"])
    if not den:
        return None
    return secs / den * params.get("scale", 1.0)
