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
  had to do (FLOPs over the peak FLOP/s or bytes over the peak bytes/s)
  over their time. ``roofline.work`` names the function that knows the
  work: ``f(params, ctx, trace) -> (least seconds, the calls' seconds)``,
  looked for first in the cell's architecture module
  (``ctx["architecture"]``), then among those here, so a kernel that
  one architecture brings needs no entry in this file. Work counted from
  the trace's own calls (``flash_flops``) takes whole events only, calls
  and seconds alike.

A pattern that matches nothing reads nothing, and says so on an earlier
line of the output: the program's names have changed under the metric.
So does a ``roofline.work`` that neither place has.
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
    """Bytes the paged decode kernel had to read (every live cached token
    of every slot, K and V, once a layer a decode step: the driver's sum
    over the steps it waited for of live tokens, times the architecture's
    cache bytes a token) against every call's time."""
    byts = ctx["counters"]["kv_token_steps"] \
        * ctx["architecture"].kv_bytes_per_token(ctx["config"])
    secs = trace.matching(params["line"], params["pattern"])[0]
    return byts / ctx["peaks"]["hbm_bytes"], secs


def _train_step(params, ctx, trace):
    """The whole step's share of the peak (MFU): the model FLOPs of a
    step's tokens, by the architecture's count at the mix's length, for
    each whole step event, against those events' time."""
    mix = ctx["mix"]
    per_step = mix["batch"] * mix["seq_len"] * ctx[
        "architecture"].model_flops_per_token(ctx["config"], mix["seq_len"])
    secs, n = trace.matching(params["line"], params["pattern"], whole=True)
    return n * per_step / ctx["peaks"]["flops"], secs


def _decode_step(params, ctx, trace):
    """The decode program's share of the peak (MFU): two FLOPs a
    multiplied parameter for every token the slots decoded while
    tracing, and attention's two products over the live tokens each
    read (``kv_token_steps``), against the program's time."""
    c, n = ctx["config"], ctx["counters"]
    flops = (2.0 * ctx["architecture"].param_count(c, active=True)
             * n["traced_tokens_decoded"]
             + work.decode_attn_flops(n["kv_token_steps"], c))
    secs = trace.matching(params["line"], params["pattern"])[0]
    return flops / ctx["peaks"]["flops"], secs


LEAST_TIME = {"flash_flops": _flash, "paged_attn_bytes": _paged,
              "train_step_flops": _train_step,
              "decode_step_flops": _decode_step}


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
        name = params["roofline"]["work"]
        fn = getattr(ctx.get("architecture"), name, None) \
            or LEAST_TIME.get(name)
        if fn is None:
            say(f"trace_ops: NOTHING computes the work {name!r}: neither "
                f"the cell's architecture module nor trace_ops has such a "
                f"function; the metric is left out")
            return None
        least, secs = fn(params, ctx, trace)
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
