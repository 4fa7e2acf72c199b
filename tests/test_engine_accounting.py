"""The seam between the serving scheduler and the planes that hear it.

``ServingEngine`` reports each event once to ``inference/accounting.py``.
Held here: every way a request can end writes exactly one terminal
record in every plane; with the monitor off and nothing attached no
plane is called at all, and the tokens are the monitor-on run's; and the
once-a-chunk hand-over gives each request the totals that the per-slot
tick inside the emit loop gave at the parent of PR 29.
"""
import time
from collections import Counter

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.distributed import introspect
from paddle_tpu.inference import (EngineOverloaded, Request,
                                  RequestRejected, ServingEngine,
                                  accounting, failover)
from paddle_tpu.models import falcon_h1 as F
from paddle_tpu.models import llama as L
from paddle_tpu.models import moe as M
from paddle_tpu.monitor import (exectime, forensics, numerics, programs,
                                server, slo, trace)


@pytest.fixture
def mon():
    monitor.reset()
    pt.set_flags({"FLAGS_enable_monitor": True})
    yield monitor
    server.unregister_health_provider("slo_burn")
    slo._PROVIDER_REGISTERED[0] = False
    pt.set_flags({"FLAGS_enable_monitor": False})
    monitor.reset()


@pytest.fixture(scope="module")
def llama():
    cfg = L.llama_tiny()
    return L, cfg, L.init_params(cfg, jax.random.PRNGKey(3))


def request(rid, n=5, new=4, **kw):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, prompt=rng.integers(0, 256, n).astype(np.int32),
                   max_new_tokens=new, **kw)


# -- (c) every request ends once ---------------------------------------------

def end_completed(eng):
    eng.run([request(0)])
    return 0, None


def end_completed_after_preemption(eng):
    # 2 slots on a 5-page pool: both prompts fit, both growing past 8
    # positions cannot, so the reserve of a chunk preempts one
    eng.run([request(0, 5, 8), request(1, 5, 8)])
    assert eng.stats.preempted >= 1
    victim = max(eng.outputs.values(), key=lambda o: o.preemptions)
    assert victim.preemptions >= 1
    return victim.rid, None


def end_rejected(eng):
    bad = Request(rid=0, prompt=np.zeros(0, np.int32), max_new_tokens=2)
    with pytest.raises(RequestRejected) as err:
        eng.submit(bad)
    assert not isinstance(err.value, EngineOverloaded)
    return 0, err.value


def end_shed_at_a_draining_engine(eng):
    eng.begin_drain()
    with pytest.raises(EngineOverloaded) as err:
        eng.submit(request(0))
    assert err.value.retry_after_s >= 0.0
    return 0, err.value


def end_shed_by_displacement(eng):
    eng.submit(request(0, priority=0))
    eng.submit(request(1, priority=5))      # the queue holds one
    assert eng.outputs[0].shed_reason.startswith("displaced")
    assert eng.outputs[0].retry_after_s is not None
    eng.run()
    return 0, None


def end_expired_in_the_queue(eng):
    eng.submit(request(0, deadline_s=1e-4))
    time.sleep(0.005)
    eng.run()
    assert len(eng.outputs[0].tokens) == 0
    return 0, None


def end_expired_in_a_slot(eng):
    req = request(0, new=12, deadline_s=1e4)
    eng.submit(req)
    assert eng.step() and eng.slots[0] is not None
    req._t_deadline = time.perf_counter() - 1.0     # the deadline passes
    eng.run()
    assert 1 <= len(eng.outputs[0].tokens) < 12     # its tokens are kept
    return 0, None


ENDINGS = {
    # name: (engine keywords, scenario, state, the ring's instant)
    "completed": ({}, end_completed, "completed", "serving.retire"),
    "completed_after_preemption": (
        dict(max_len=16, num_pages=5, decode_chunk=2),
        end_completed_after_preemption, "completed", "serving.retire"),
    "rejected": ({}, end_rejected, "rejected", "serving.reject"),
    "shed_draining": ({}, end_shed_at_a_draining_engine, "shed",
                      "serving.shed"),
    "shed_displaced": (dict(max_queue=1), end_shed_by_displacement, "shed",
                       "serving.shed"),
    "expired_in_queue": ({}, end_expired_in_the_queue, "expired",
                         "serving.expire"),
    "expired_in_slot": (dict(decode_chunk=2), end_expired_in_a_slot,
                        "expired", "serving.expire"),
}


@pytest.mark.serving
@pytest.mark.parametrize("ending", ENDINGS)
def test_every_request_ends_once(mon, llama, ending, tmp_path, monkeypatch):
    family, cfg, params = llama
    kw, scenario, state, instant = ENDINGS[ending]
    eng = ServingEngine(family, params, cfg, failover=True, **dict(
        dict(num_slots=2, max_len=32, page_size=4), **kw))
    journal = eng.attach_journal("replica", str(tmp_path))
    calls = Counter()

    def counting(owner, name, key):
        real = getattr(owner, name)

        def counted(*args, **kwargs):
            calls[key(*args, **kwargs)] += 1
            return real(*args, **kwargs)
        monkeypatch.setattr(owner, name, counted)

    # forensics drops a second terminal of a rid itself, and the journal
    # overwrites a marker: count the calls, not what is left
    counting(forensics, "note_terminal", lambda rid, st, **k: ("forensics",
                                                               rid, st))
    counting(journal, "finish", lambda rid, st, **k: ("journal", rid, st))
    counting(slo, "record_request", lambda rec: (
        "slo", "shed" if rec.get("shed") else "rejected"
        if rec.get("rejected") else "expired" if rec.get("expired")
        else "completed"))

    rid, raised = scenario(eng)

    entered = raised is None
    others = len(eng.outputs) - (1 if entered else 0)   # all completed
    assert calls["forensics", rid, state] == 1
    assert forensics.request_payload(rid)["state"] == state
    assert calls["journal", rid, state] == (1 if entered else 0)
    marker = failover.read_journal("replica", dir_path=str(tmp_path))
    if entered:
        assert marker["completed"][str(rid)]["state"] == state
        assert marker["inflight"] == {}
    else:
        assert marker is None or str(rid) not in marker["completed"]
    assert calls["slo", state] == 1 + (others if state == "completed"
                                       else 0)
    assert sum(n for k, n in calls.items() if k[0] == "slo") \
        == 1 + others == len(slo.records())
    counters = monitor.snapshot()["counters"]
    assert counters[f"serving.requests.{state}"] == calls["slo", state]
    ring = [e for e in trace.events() if e["name"] == instant
            and e["args"]["rid"] == rid]
    assert len(ring) == 1
    if entered:
        out = eng.outputs[rid]
        assert out.finish_reason == state
        assert (out.cost is not None) and out.cost.preemptions \
            == out.preemptions
        assert getattr(eng.stats, state) == calls["slo", state]
    else:
        assert rid not in eng.outputs and raised.rid == rid
    assert eng.drain_complete
    eng.cache.alloc.check_invariants()


# -- (d) monitor off: no plane is called -------------------------------------

FAMILIES = {
    "llama": (L, L.llama_tiny, {}),
    "moe": (M, M.moe_tiny, {}),
    "falcon_h1": (F, F.falcon_h1_tiny, dict(max_len=64, page_size=8)),
}


class Off:
    """Stands where a plane's module stood: the ``allowed`` names pass
    through, any other name is a call that the monitor-off path made."""

    def __init__(self, real, allowed=()):
        self._real, self._allowed = real, allowed

    def __getattr__(self, name):
        if name in self._allowed:
            return getattr(self._real, name)
        raise AssertionError(
            f"{self._real.__name__}.{name} reached with the monitor off")


def serve(family, cfg, params, temperature, **kw):
    eng = ServingEngine(family, params, cfg, **dict(
        dict(num_slots=2, max_len=32, page_size=4, decode_chunk=2), **kw))
    for rid, (n, new) in enumerate([(5, 7), (3, 5), (6, 4)]):
        eng.submit(request(rid, n, new, temperature=temperature,
                           key=jax.random.PRNGKey(rid) if temperature
                           else None))
    eng.run()
    assert eng.stats.completed == 3
    return eng, {rid: o.tokens.tolist() for rid, o in eng.outputs.items()}


@pytest.mark.serving
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("name", FAMILIES)
def test_monitor_off_does_no_accounting(name, temperature, monkeypatch):
    family, tiny, kw = FAMILIES[name]
    cfg = tiny()
    params = family.init_params(cfg, jax.random.PRNGKey(3))
    monitor.reset()
    pt.set_flags({"FLAGS_enable_monitor": True})
    try:
        eng, on = serve(family, cfg, params, temperature, **kw)
        assert all(o.cost is not None for o in eng.outputs.values())
    finally:
        pt.set_flags({"FLAGS_enable_monitor": False})
        monitor.reset()

    with monkeypatch.context() as m:
        m.setattr(accounting, "_monitor", Off(
            monitor, allowed=("enabled", "programs")))
        m.setattr(accounting, "_mserver", Off(
            server, allowed=("maybe_start", "plane_active")))
        for plane in ("_forensics", "_slo", "_trace"):
            m.setattr(accounting, plane, Off(getattr(accounting, plane)))
        # what the accounting imports only when it needs it
        for owner, names in ((programs, ("record_jit_call", "has_record",
                                         "flops_of")),
                             (exectime, ("maybe_sample",)),
                             (numerics, ("kv_sample_rate",)),
                             (introspect, ("register_sharded_tree",
                                           "ensure_sharded_tree")),
                             (failover, ("AdmissionJournal",))):
            for attr in names:
                m.setattr(owner, attr, _raiser(owner, attr))
        eng, off = serve(family, cfg, params, temperature, **kw)
    assert off == on
    assert all(o.cost is None for o in eng.outputs.values())
    assert eng._acct.journal is None and eng._acct.frame_pub is None
    assert not monitor.snapshot().get("counters")


def _raiser(owner, attr):
    def reached(*args, **kwargs):
        raise AssertionError(
            f"{owner.__name__}.{attr} reached with the monitor off")
    return reached


# -- (e) the once-a-chunk hand-over ------------------------------------------

# {rid: (decode_tokens, slot_steps, page_seconds, grid_steps)} of each
# request under a clock that moves one second an engine step, taken on
# the parent of PR 29 (commit 6b56da2), whose per-slot tick sat inside
# the emit loops of _chunk_step and _spec_step
CHUNK_PATHS = {
    "plain": (dict(), [(5, 6), (3, 4), (6, 5)], 2,
              {0: (5, 6, 9.0, 12), 1: (3, 4, 4.0, 8), 2: (4, 4, 6.0, 8)}),
    "turbo": (dict(), [(5, 20), (6, 20)], 8,
              {0: (19, 20, 26.0, 40), 1: (19, 20, 26.0, 40)}),
    "verify": (dict(spec_decode=True), [(5, 20), (6, 20)], "verify",
               {0: (19, 82, 74.0, 164), 1: (19, 78, 67.0, 156)}),
}


@pytest.mark.serving
@pytest.mark.parametrize("path", CHUNK_PATHS)
def test_chunk_accounting_totals(mon, llama, path, monkeypatch):
    family, cfg, params = llama
    kw, sizes, took, expected = CHUNK_PATHS[path]
    clock = [100.0]
    monkeypatch.setattr(time, "perf_counter", lambda: clock[0])
    eng = ServingEngine(family, params, cfg, num_slots=2, max_len=64,
                        page_size=4, decode_chunk=2, **kw)
    rng = np.random.default_rng(1)
    for rid, (n, new) in enumerate(sizes):
        eng.submit(Request(rid=rid, max_new_tokens=new, prompt=rng.integers(
            0, cfg.vocab_size, n).astype(np.int32)))
    while eng.step():
        clock[0] += 1.0
    # the path under test did run: its program was called
    called = {id(f) for f in eng._spec_fns.values()} if took == "verify" \
        else {id(eng._chunk_fns[(took, False)])}
    assert called and called <= eng._called
    got = {rid: (o.cost.decode_tokens, o.cost.slot_steps,
                 o.cost.page_seconds, o.cost.grid_steps)
           for rid, o in eng.outputs.items()}
    assert got == expected
