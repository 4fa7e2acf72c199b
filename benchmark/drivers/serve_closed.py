"""Closed loop: a client a slot, each sending its next request when its
last one completes. The slots are filled during set-up with requests met
part-way through, and the window opens once every client's first request
has its slot."""
from __future__ import annotations

import time

from ..harness import traffic
from ..harness.serving import Serving
from ..harness.session import say


def run(r) -> dict:
    mix = r.mix
    clients = r.conf["serve"]["num_slots"]
    sched = traffic.closed_schedule(mix, clients)
    top = mix["prompt"]["max"] + mix["output"]["max"] - 2
    sv = Serving(r, mix["prompt"]["min"], top)
    prompts = traffic.prompt_ids(sched["prompt_len"], sv.vocab, r.seed)
    out_len = sched["out_len"]
    n, nxt = len(out_len), 0
    t_zero = [None]

    def clock():
        return time.perf_counter() - (t_zero[0] or 0.0)

    inflight, counted, step_s = set(), [], []
    first = sv._next_rid             # the first client's first request
    free = clients
    delivered0 = t_close = None
    while True:
        opened = t_zero[0] is not None
        now = clock() if opened else 0.0
        if opened:
            r.tracer.tick(now)
            if now >= r.seconds:
                break
        while free and sv.can_submit(len(prompts[nxt % n])):
            # (a system that outruns the mix's list meets it again)
            inflight.add(sv.submit(prompts[nxt % n], out_len[nxt % n]))
            nxt, free = nxt + 1, free - 1
        sv.step(clock)
        if opened:
            step_s.append(clock() - now)
        done = {rid for rid in inflight if sv.finished(rid)}
        inflight -= done
        free += len(done)
        if opened:
            counted += done
        elif nxt >= clients and all(rid in sv.t_first
                                    for rid in range(first, first + clients)):
            # every client's first request has its slot and decodes
            t_zero[0] = time.perf_counter()
            r.open_window()
            c0, delivered0 = sv.counters(), sv.delivered
    t_close = clock()
    r.tracer.stop(t_close)
    c1 = sv.counters()
    tokens = sv.delivered - delivered0
    attempted, failed = sv.verdict(counted)
    say(f"closed loop: {tokens} tokens delivered in {t_close:.3f} s; "
        f"{attempted} requests completed in the window, {failed} failed; "
        f"{nxt} handed out from a list of {n}")
    # (a run that reads far off shows here whether one step stalled or
    # all were slow)
    say(f"steps in the window, seconds each: "
        f"{[round(x, 3) for x in step_s]}")
    return {
        "correct": sv.check["ok"] and failed == 0 and not sv.rejected,
        "attempted": attempted, "failed": failed,
        "end_to_end": {"serve_tok_s": tokens / t_close},
        "counters": sv.window_counters(c0, c1, t_close),
        "compared": {**sv.check["numbers"], "requests_failed": [failed, 0],
                     "requests_rejected": [len(sv.rejected), 0]},
        "info": {"check": sv.check, "warm": sv.warmed,
                 "rejected": sv.rejected[:5]},
    }
