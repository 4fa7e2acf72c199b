"""Exactly-once request failover (paddle_tpu/inference/failover.py).

The contract under test, mechanism by mechanism on stubs (no model,
no wall clock — the coordinator and breaker take injected time):

- admission journal: write-through publish on the name-keyed
  heartbeat transport, completion markers at retirement, bounded
  marker window, future-format refusal, honest degradation when the
  transport fails;
- exactly-once dedup: a rid carrying a completion marker in the
  crash-window payload is never re-dispatched;
- stranded-work re-dispatch: backoff scheduling in coordinator-clock
  seconds, bounded attempts ending in a typed terminal shed,
  ``retry_after_s`` hints clamped to the backoff cap, lineage in
  ``recovered_from``;
- poison quarantine: the attempt ladder AND the content-hash set (a
  retry under a fresh rid still hits it);
- circuit breakers: closed -> open on consecutive sheds -> half-open
  after cooldown -> single probe -> closed or reopened.

Plus the real-engine seam: journal round trip through submit/retire,
and the re-submission safety fix (per-run mutable state reset + the
pinned PRNG key making a resubmitted sampled request byte-identical).
"""
import numpy as np
import pytest

from paddle_tpu.distributed import heartbeat as hb
from paddle_tpu.inference import failover as fo


class _Req:
    """Duck-typed request: exactly the attributes the journal reads."""

    def __init__(self, rid, prompt=(1, 2, 3), max_new_tokens=4,
                 temperature=0.0, tenant="t0", priority=0,
                 deadline_s=None, prompt_spec=None, key=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.tenant = tenant
        self.priority = priority
        self.deadline_s = deadline_s
        self.prompt_spec = prompt_spec
        self.key = key


def _journal(tmp_path, replica="r0", **kw):
    return fo.AdmissionJournal(replica, dir_path=str(tmp_path), **kw)


def _coord(tmp_path, **kw):
    kw.setdefault("heartbeat_dir", str(tmp_path))
    return fo.FailoverCoordinator(**kw)


# ---------------------------------------------------------------------------
# admission journal
# ---------------------------------------------------------------------------

class TestAdmissionJournal:
    def test_round_trip_and_completion_marker(self, tmp_path):
        j = _journal(tmp_path)
        j.admit(_Req(7, prompt=(4, 5), max_new_tokens=6,
                     deadline_s=1.5, priority=2,
                     prompt_spec={"seed": 3, "rid": 7,
                                  "prompt_len": 2, "vocab": 32}))
        j.admit(_Req(8))
        payload = fo.read_journal("r0", dir_path=str(tmp_path))
        assert payload["kind"] == fo.JOURNAL_KIND
        assert set(payload["inflight"]) == {"7", "8"}
        rec = payload["inflight"]["7"]
        assert rec["tenant"] == "t0" and rec["priority"] == 2
        assert rec["deadline_s"] == 1.5
        assert rec["prompt_spec"]["seed"] == 3
        assert "prompt" not in rec          # spec replaces inline tokens
        assert rec["idem"] == f"7:{rec['fingerprint']}"
        # rid 8 has no spec: inline tokens journaled instead
        assert payload["inflight"]["8"]["prompt"] == [1, 2, 3]

        j.finish(7, "completed", tokens=6)
        payload = fo.read_journal("r0", dir_path=str(tmp_path))
        assert set(payload["inflight"]) == {"8"}
        marker = payload["completed"]["7"]
        assert marker["state"] == "completed" and marker["tokens"] == 6
        assert marker["idem"] == rec["idem"]

        fo.sweep_journal("r0", dir_path=str(tmp_path))
        assert fo.read_journal("r0", dir_path=str(tmp_path)) is None

    def test_fingerprint_is_content_keyed(self):
        a = fo.request_fingerprint(np.asarray([1, 2], np.int32), 4, 0.0)
        b = fo.request_fingerprint(np.asarray([1, 2], np.int32), 4, 0.0)
        c = fo.request_fingerprint(np.asarray([1, 3], np.int32), 4, 0.0)
        d = fo.request_fingerprint(np.asarray([1, 2], np.int32), 5, 0.0)
        assert a == b
        assert len({a, c, d}) == 3

    def test_completed_window_bounded(self, tmp_path):
        j = _journal(tmp_path, max_completed=3)
        for rid in range(6):
            j.admit(_Req(rid))
            j.finish(rid, "completed", tokens=1)
        assert list(j.completed) == ["3", "4", "5"]

    def test_future_version_refused(self, tmp_path):
        hb.publish_named(fo.journal_name("rz"),
                         {"kind": fo.JOURNAL_KIND, "v": 99,
                          "inflight": {}, "completed": {}},
                         dir_path=str(tmp_path))
        assert fo.read_journal("rz", dir_path=str(tmp_path)) is None

    def test_publish_failure_degrades_not_raises(self, tmp_path,
                                                 monkeypatch):
        j = _journal(tmp_path)

        def boom(*a, **k):
            raise OSError("transport down")

        monkeypatch.setattr(hb, "publish_named", boom)
        j.admit(_Req(1))            # must not raise
        j.finish(1, "completed")
        assert j.publish_failures == 2


# ---------------------------------------------------------------------------
# coordinator: strand / dedup / backoff / quarantine
# ---------------------------------------------------------------------------

class TestCoordinator:
    def test_strand_with_lineage_and_backoff(self, tmp_path):
        j = _journal(tmp_path, "victim")
        j.admit(_Req(3))
        c = _coord(tmp_path)
        assert c.note_replaced("victim", now=10.0) == 1
        assert c.counters["stranded"] == 1
        (rec,) = c.pending
        assert rec["recovered_from"] == ["victim"]
        assert rec["attempts"] == 1
        assert rec["not_before"] == pytest.approx(10.25)  # 0.25 * 2^0
        assert c.due(10.0) == [] and len(c.pending) == 1
        assert [r["rid"] for r in c.due(10.3)] == [3]
        assert c.outstanding() == 0
        # the consumed journal is swept: a second replace finds nothing
        assert c.note_replaced("victim", now=11.0) == 0

    def test_dedup_on_completion_marker(self, tmp_path):
        # crash-window overlap: the payload carries rid 5 in BOTH maps
        # (finished just before the crash, marker published, inflight
        # copy one event stale) — the marker wins, never re-served
        j = _journal(tmp_path, "victim")
        j.admit(_Req(5))
        j.admit(_Req(6))
        payload = fo.read_journal("victim", dir_path=str(tmp_path))
        payload["completed"]["5"] = {"state": "completed", "tokens": 4}
        hb.publish_named(fo.journal_name("victim"), payload,
                         dir_path=str(tmp_path))
        c = _coord(tmp_path)
        assert c.note_replaced("victim", now=0.0) == 1
        assert c.counters["deduped"] == 1
        assert [r["rid"] for r in c.pending] == [6]

    def test_quarantine_ladder_and_hash_set(self, tmp_path):
        c = _coord(tmp_path, quarantine_attempts=2)
        req = _Req(9, prompt=(7, 7, 7))
        _journal(tmp_path, "r0").admit(req)
        assert c.note_replaced("r0", now=0.0) == 1
        (rec,) = c.due(1.0)
        c.redispatched(rec, "r1", 1.0)
        # the survivor dies too, its journal carrying the same record
        j1 = fo.AdmissionJournal("r1", dir_path=str(tmp_path))
        j1.inflight["9"] = dict(rec)
        j1._publish()
        assert c.note_replaced("r1", now=2.0) == 1
        term = c.terminal[9]
        assert term["state"] == "quarantined"
        assert term["recovered_from"] == ["r0", "r1"]
        assert c.counters["quarantined"] == 1
        # content hash is poisoned: the SAME prompt under a fresh rid
        # quarantines immediately, without climbing the ladder
        fresh = _Req(55, prompt=(7, 7, 7))
        _journal(tmp_path, "r2").admit(fresh)
        c.note_replaced("r2", now=3.0)
        assert c.terminal[55]["state"] == "quarantined"
        assert c.counters["quarantined"] == 2

    def test_restrand_after_survivor_death(self, tmp_path):
        # a re-dispatched rid whose survivor dies is re-stranded from
        # the survivor's journal, not skipped as already-known
        c = _coord(tmp_path, quarantine_attempts=5)
        _journal(tmp_path, "r0").admit(_Req(1))
        c.note_replaced("r0", now=0.0)
        (rec,) = c.due(1.0)
        c.redispatched(rec, "r1", 1.0)
        j1 = fo.AdmissionJournal("r1", dir_path=str(tmp_path))
        j1.inflight["1"] = dict(rec)
        j1._publish()
        assert c.note_replaced("r1", now=2.0) == 1
        (again,) = c.pending
        assert again["attempts"] == 2
        assert again["recovered_from"] == ["r0", "r1"]

    def test_requeue_attempt_bound_and_hint_clamp(self, tmp_path):
        c = _coord(tmp_path, max_attempts=3, backoff_cap_s=5.0)
        rec = {"rid": 4, "attempts": 1, "tenant": "t0"}
        c.requeue(dict(rec), 0.0, retry_after_s=60.0)
        (q,) = c.pending
        assert q["not_before"] == pytest.approx(5.0)   # clamped to cap
        c.pending.clear()
        c.requeue(dict(rec, attempts=2), 0.0)          # hits the bound
        assert not c.pending
        assert c.terminal[4]["state"] == "shed"
        assert c.counters["shed"] == 1

    def test_resolve_expired_and_note_result(self, tmp_path):
        c = _coord(tmp_path)
        rec = {"rid": 2, "attempts": 1, "tenant": "t0"}
        c.resolve(dict(rec), "expired")
        assert c.terminal[2]["state"] == "expired"
        assert c.counters["expired"] == 1
        c.redispatched({"rid": 3, "attempts": 1}, "r0", 0.0)
        c.note_result(3, "completed")
        assert c.counters["recovered"] == 1
        c.note_result(3, "completed")            # idempotent
        assert c.counters["recovered"] == 1

    def test_snapshot_shape(self, tmp_path):
        c = _coord(tmp_path)
        c.resolve({"rid": 1, "attempts": 1}, "expired")
        c.admission_result("r0", False, 0.0)
        snap = c.snapshot()
        assert snap["terminal_by_state"] == {"expired": 1}
        assert snap["pending"] == 0
        assert snap["counters"]["expired"] == 1
        assert snap["breakers"]["r0"]["state"] == "closed"


# ---------------------------------------------------------------------------
# circuit breakers
# ---------------------------------------------------------------------------

class TestCircuitBreaker:
    def test_open_halfopen_close_cycle(self):
        b = fo.CircuitBreaker(threshold=3, cooldown_s=2.0)
        for _ in range(2):
            b.record(False, 0.0)
        assert b.state == "closed"
        b.record(True, 0.0)              # success resets the streak
        for _ in range(3):
            b.record(False, 1.0)
        assert b.state == "open" and b.opened_count == 1
        assert not b.allows(2.0)         # still inside the cooldown
        assert b.allows(3.0)             # cooldown elapsed -> half_open
        assert b.state == "half_open"
        b.note_probe()
        assert not b.allows(3.0)         # single probe in flight
        b.record(True, 3.1)
        assert b.state == "closed" and b.closed_count == 1

    def test_probe_failure_reopens(self):
        b = fo.CircuitBreaker(threshold=1, cooldown_s=1.0)
        b.record(False, 0.0)
        assert b.state == "open"
        assert b.allows(1.5)
        b.note_probe()
        b.record(False, 1.5)
        assert b.state == "open" and b.opened_count == 2
        assert not b.allows(2.0)
        assert b.allows(2.5)

    def test_pick_replica_routes_around_open_breaker(self, tmp_path):
        c = _coord(tmp_path, breaker_threshold=2,
                   breaker_cooldown_s=100.0)
        for _ in range(2):
            c.admission_result("r1", False, 0.0)
        assert c.breakers["r1"].state == "open"
        live = ["r0", "r1", "r2"]
        picks = {c.pick_replica(live, rid, now=1.0) for rid in range(6)}
        assert picks == {"r0", "r2"}

    def test_pick_replica_falls_back_when_all_open(self, tmp_path):
        c = _coord(tmp_path, breaker_threshold=1,
                   breaker_cooldown_s=100.0)
        for n in ("r0", "r1"):
            c.admission_result(n, False, 0.0)
        # routing away from everyone is routing to no one: fall back
        assert c.pick_replica(["r0", "r1"], 0, now=1.0) in ("r0", "r1")

    def test_replaced_replica_breaker_dropped(self, tmp_path):
        c = _coord(tmp_path, breaker_threshold=1)
        c.admission_result("victim", False, 0.0)
        assert "victim" in c.breakers
        c.note_replaced("victim", now=1.0)
        assert "victim" not in c.breakers


# ---------------------------------------------------------------------------
# monitor-plane surface
# ---------------------------------------------------------------------------

class TestFederationSurface:
    def test_fleet_serving_snapshot_failover_block(self, tmp_path):
        # the /fleet/serving payload grows a failover block only while
        # a coordinator is registered — absent otherwise, so flags-off
        # payloads are byte-identical
        from paddle_tpu.monitor import federation as fed
        c = _coord(tmp_path)
        c.resolve({"rid": 1, "attempts": 1}, "expired")
        fo.set_active_coordinator(c)
        try:
            snap = fed.fleet_serving_snapshot()
            assert snap["failover"]["terminal_by_state"] == {
                "expired": 1}
        finally:
            fo.set_active_coordinator(None)
        assert "failover" not in fed.fleet_serving_snapshot()

    def test_active_coordinator_is_weakref(self, tmp_path):
        import gc
        c = _coord(tmp_path)
        fo.set_active_coordinator(c)
        assert fo.active_coordinator() is c
        del c
        gc.collect()
        assert fo.active_coordinator() is None
        fo.set_active_coordinator(None)


# ---------------------------------------------------------------------------
# real-engine seam: journal wiring + re-submission safety
# ---------------------------------------------------------------------------

def _mk_engine(**kw):
    import jax
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import llama as L
    cfg = L.llama_tiny(num_hidden_layers=1)
    params = L.init_params(cfg, jax.random.PRNGKey(3))
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 24)
    kw.setdefault("page_size", 4)
    kw.setdefault("decode_chunk", 2)
    return ServingEngine(L, params, cfg, **kw)


def _drain(eng, limit=200):
    for _ in range(limit):
        if not eng.step():
            return
    raise AssertionError("engine did not go idle")


@pytest.mark.serving
class TestEngineJournalSeam:
    def test_submit_journals_and_retire_markers(self, tmp_path):
        from paddle_tpu.inference.engine import Request
        eng = _mk_engine(failover=True)
        assert eng.attach_journal("rA", str(tmp_path)) is not None
        eng.submit(Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=3, tenant="t0"))
        payload = fo.read_journal("rA", dir_path=str(tmp_path))
        assert set(payload["inflight"]) == {"0"}
        _drain(eng)
        payload = fo.read_journal("rA", dir_path=str(tmp_path))
        assert payload["inflight"] == {}
        marker = payload["completed"]["0"]
        assert marker["state"] == "completed"
        assert marker["tokens"] == len(eng.outputs[0].tokens)

    def test_flags_off_attach_is_noop(self, tmp_path):
        eng = _mk_engine()                      # failover defaults off
        assert eng._failover is False
        assert eng.attach_journal("rB", str(tmp_path)) is None
        assert eng._acct.journal is None

    def test_resubmission_resets_state_and_pins_tokens(self, tmp_path):
        # satellite contract: a Request object re-admitted after a
        # strand starts clean (timing/cost/preemption state reset) and
        # — because submit pinned the sampling key on first admission —
        # replays byte-identical tokens on the survivor
        from paddle_tpu.inference.engine import Request
        a = _mk_engine(failover=True)
        a.attach_journal("rA", str(tmp_path))
        req = Request(rid=1, prompt=np.arange(1, 6, dtype=np.int32),
                      max_new_tokens=4, temperature=0.8)
        assert req.key is None
        a.submit(req)
        assert req.key is not None              # pinned at admission
        key0 = np.asarray(req.key).copy()
        _drain(a)
        first = list(a.outputs[1].tokens)
        # simulate the state a monitored/preempted run leaves behind
        # (the accounting's record, timing anchors and cost, exists
        # only with the monitor on: inference/accounting.py keeps it
        # on the request as ``_acct``)
        req._acct = object()
        req._t_deadline = 125.0
        req._preempt_count = 2

        b = _mk_engine(failover=True)
        b.submit(req)                           # re-admission resets
        assert req._acct is None and req._t_deadline is None
        assert req._preempt_count == 0
        np.testing.assert_array_equal(np.asarray(req.key), key0)
        _drain(b)
        assert list(b.outputs[1].tokens) == first
