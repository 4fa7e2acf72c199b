"""Loadgen harness: deterministic trace generation, open-loop replay
and the SLO scorecard (paddle_tpu/loadgen/).

The determinism contract under test: same seed ⇒ byte-identical
serialized trace AND identical terminal-state/token counts across two
replays on fresh engines (the scorecard's ``deterministic`` block is
diffed wholesale); wall-clock data stays quarantined in ``timing``.
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.loadgen import (ArrivalTrace, Episode, TenantSpec,
                                build_scorecard, generate_trace,
                                heavy_tailed_lengths,
                                mixed_length_trace, prompt_tokens,
                                replay_fleet, replay_trace)
from paddle_tpu.loadgen import scorecard as sc
from paddle_tpu.loadgen.traces import TRACE_VERSION

# the draw sequence the packed-training bench rung and the smoke
# pre-tuning were swept under (autotune cache keys depend on it) —
# pinned byte-for-byte, see io/packing.py heavy_tailed_lengths
HEAVY_TAILED_GOLDEN_2048_24_7 = [
    512, 1024, 512, 128, 128, 1024, 128, 1024, 512, 256, 128, 128,
    128, 256, 256, 256, 2048, 512, 512, 2048, 128, 128, 512, 128]


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------

class TestTraces:
    def test_heavy_tailed_pinned_golden(self):
        assert heavy_tailed_lengths(2048, 24, seed=7) \
            == HEAVY_TAILED_GOLDEN_2048_24_7

    def test_packing_delegate_is_byte_identical(self):
        # io.packing re-exports the loadgen implementation: the
        # historical import path must keep the exact draw sequence
        from paddle_tpu.io import packing as pk
        for args in ((2048, 24, 7), (512, 16, 3), (128, 40, 11)):
            assert pk.heavy_tailed_lengths(*args) \
                == heavy_tailed_lengths(*args)

    def test_mixed_length_trace_matches_inline_construction(self):
        # parity with the serving_paged rung's historical inline code,
        # including draw-sequence continuation: the bench passes its
        # live Generator and draws prompt tokens AFTER the trace, so
        # the helper must consume exactly the same number of draws
        plens, glens, n = (4, 8, 16), (4, 8, 16, 64), 32
        ref_rng = np.random.default_rng(42)
        ref = [(int(ref_rng.choice(plens)), int(ref_rng.choice(glens)))
               for _ in range(n)]
        ref.sort(key=lambda t: -t[1])
        rng = np.random.default_rng(42)
        got = mixed_length_trace(plens, glens, n, rng)
        assert got == ref
        np.testing.assert_array_equal(rng.integers(0, 1000, (8,)),
                                      ref_rng.integers(0, 1000, (8,)))

    def test_mixed_length_trace_accepts_int_seed(self):
        assert mixed_length_trace((4, 8), (4, 16), 10, 5) \
            == mixed_length_trace((4, 8), (4, 16), 10,
                                  np.random.default_rng(5))

    def test_same_seed_byte_identical_json(self):
        kw = dict(duration_s=1.0, rate=32.0,
                  tenants=[TenantSpec("a", priority=1),
                           TenantSpec("b", share=2.0,
                                      deadline_s=5.0)],
                  burst=(0.4, 0.2, 3.0))
        a, b = generate_trace(11, **kw), generate_trace(11, **kw)
        assert a.to_json() == b.to_json()
        assert a.sha256() == b.sha256()

    def test_different_seed_differs(self):
        assert generate_trace(11).to_json() \
            != generate_trace(12).to_json()

    def test_json_round_trip(self):
        tr = generate_trace(21, tenants=[TenantSpec("x", priority=3,
                                                    deadline_s=2.0)],
                            burst=(0.2, 0.1, 4.0))
        back = ArrivalTrace.from_json(tr.to_json())
        assert back.to_json() == tr.to_json()
        assert back.requests[0] == tr.requests[0]
        assert back.config == tr.config

    def test_newer_version_refused(self):
        tr = generate_trace(3, duration_s=0.1, rate=10.0)
        d = tr.as_dict()
        d["version"] = TRACE_VERSION + 1
        with pytest.raises(ValueError, match="newer"):
            ArrivalTrace.from_json(json.dumps(d))

    def test_burst_window_concentrates_arrivals(self):
        quiet = generate_trace(7, duration_s=1.0, rate=40.0)
        burst = generate_trace(7, duration_s=1.0, rate=40.0,
                               burst=(0.4, 0.2, 5.0))

        def in_window(tr):
            return sum(0.4 <= r.arrival_s < 0.6 for r in tr.requests)

        assert in_window(burst) > 2 * in_window(quiet)
        assert len(burst.requests) > len(quiet.requests)

    def test_tenant_mix_carries_priority_and_deadline(self):
        tr = generate_trace(9, duration_s=1.0, rate=64.0,
                            tenants=[TenantSpec("rt", priority=5,
                                                deadline_s=0.5),
                                     TenantSpec("bg", share=3.0)])
        by = {}
        for r in tr.requests:
            by.setdefault(r.tenant, []).append(r)
        assert set(by) == {"rt", "bg"}
        assert all(r.priority == 5 and r.deadline_s == 0.5
                   for r in by["rt"])
        assert all(r.priority == 0 and r.deadline_s is None
                   for r in by["bg"])
        # the 3x share tenant dominates the mix
        assert len(by["bg"]) > len(by["rt"])

    def test_lengths_respect_bounds_and_heavy_tail(self):
        tr = generate_trace(13, duration_s=2.0, rate=128.0,
                            prompt_len=(4, 64),
                            max_new_tokens=(4, 32), alpha=1.2)
        ps = [r.prompt_len for r in tr.requests]
        gs = [r.max_new_tokens for r in tr.requests]
        assert min(ps) >= 4 and max(ps) <= 64
        assert min(gs) >= 4 and max(gs) <= 32
        # heavy tail: median pinned near lo, but the tail is reached
        assert float(np.median(ps)) <= 16
        assert max(ps) >= 32

    def test_prompt_tokens_pure_function_of_seed_and_rid(self):
        a = prompt_tokens(11, 5, 16, 1000)
        b = prompt_tokens(11, 5, 16, 1000)
        np.testing.assert_array_equal(a, b)
        assert a.dtype == np.int32 and a.shape == (16,)
        assert not np.array_equal(a, prompt_tokens(11, 6, 16, 1000))

    def test_generate_trace_validates(self):
        with pytest.raises(ValueError, match="duration_s"):
            generate_trace(1, duration_s=0.0)
        with pytest.raises(ValueError, match="shares"):
            generate_trace(1, tenants=[TenantSpec("a", share=0.0)])

    def test_offered_tokens_and_tenants(self):
        tr = generate_trace(2, duration_s=0.5, rate=20.0,
                            tenants=[TenantSpec("z"), TenantSpec("a")])
        assert tr.offered_tokens() \
            == sum(r.max_new_tokens for r in tr.requests)
        assert tr.tenants() == sorted(tr.tenants())


# ---------------------------------------------------------------------------
# replay + scorecard (single engine)
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


def _small_trace(seed=77):
    return generate_trace(seed, duration_s=0.5, rate=24.0,
                          tenants=[TenantSpec("interactive",
                                              priority=2),
                                   TenantSpec("batch", share=2.0)],
                          prompt_len=(3, 8), max_new_tokens=(2, 8))


def _one_replay():
    eng = _mk_engine(priority_admission=True, max_queue=3)
    return replay_trace(
        eng, _small_trace(), dt_per_step=0.02,
        episodes=[Episode("burst", at_s=0.25, n_requests=10)])


@pytest.fixture(scope="module")
def replay_pair():
    """Two same-seed replays on FRESH engines — the determinism pair
    several tests below diff (module-scoped: the replays compile a
    model, so run them once)."""
    return _one_replay(), _one_replay()


@pytest.mark.serving
class TestReplay:
    def test_same_seed_identical_terminal_and_tokens(self, replay_pair):
        a, b = replay_pair
        assert a.trace.to_json() == b.trace.to_json()
        assert a.terminal_counts() == b.terminal_counts()
        assert a.useful_tokens() == b.useful_tokens()
        assert a.offered == b.offered
        assert a.offered_tokens == b.offered_tokens
        # full per-rid diff: state, tokens, tenant, typed reasons
        assert sorted(a.terminal) == sorted(b.terminal)
        for rid in a.terminal:
            ra = {k: v for k, v in a.terminal[rid].items()
                  if k != "retry_after_s"}    # demand-model hint is
            rb = {k: v for k, v in b.terminal[rid].items()  # timing
                  if k != "retry_after_s"}
            assert ra == rb, (rid, ra, rb)

    def test_scorecard_deterministic_block_identical(self, replay_pair):
        a, b = replay_pair
        ca = build_scorecard(a)["deterministic"]
        cb = build_scorecard(b)["deterministic"]
        assert json.dumps(ca, sort_keys=True) \
            == json.dumps(cb, sort_keys=True)

    def test_exactly_one_terminal_state_per_submission(self,
                                                       replay_pair):
        res = replay_pair[0]
        assert res.offered == len(res.trace.requests) + 10
        assert len(res.terminal) == res.offered
        states = {r["state"] for r in res.terminal.values()}
        assert states <= {"completed", "shed", "expired", "rejected"}

    def test_sheds_are_typed_with_retry_hints(self, replay_pair):
        res = replay_pair[0]
        sheds = [r for r in res.terminal.values()
                 if r["state"] == "shed"]
        assert sheds, "burst did not overload the bounded queue"
        for rec in sheds:
            assert rec.get("retry_after_s") is not None, rec
            assert rec.get("reason"), rec

    def test_scorecard_structure_and_verdict(self, replay_pair):
        card = build_scorecard(replay_pair[0])
        card = json.loads(json.dumps(card))     # wire round trip
        assert card["verdict"]["pass"], card["verdict"]
        det = card["deterministic"]
        assert det["trace"]["sha256"] == replay_pair[0].trace.sha256()
        assert det["engine_flags"]["priority_admission"] is True
        assert det["engine_flags"]["max_queue"] == 3
        assert sum(det["terminal"].values()) == det["goodput"][
            "offered_requests"]
        assert det["shed_by_reason"], det
        assert 0 < det["goodput"]["request_goodput"] < 1.0
        assert 0 < det["goodput"]["token_goodput"] <= 1.0
        assert set(det["per_tenant"]) \
            >= {"interactive", "batch", "burst"}
        assert 0 < det["fairness"]["jain_completion_index"] <= 1.0
        # episode admission counts live in the deterministic plane;
        # its SLO probe/wall stamps are quarantined in timing
        assert det["episodes"][0]["kind"] == "burst"
        assert "slo" not in det["episodes"][0]
        assert "wall_s" in card["timing"]

    def test_token_conservation(self, replay_pair):
        res = replay_pair[0]
        emitted = sum(r["tokens"] for r in res.terminal.values())
        st = res.engine_stats["engine0"]
        assert st["tokens_generated"] - st["tokens_discarded"] \
            == emitted

    def test_kill_episode_rejected_single_engine(self):
        with pytest.raises(ValueError, match="replay_fleet"):
            replay_trace(_mk_engine(), _small_trace(),
                         episodes=[Episode("kill", at_s=0.1)])

    def test_unknown_episode_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown episode"):
            Episode("explode", at_s=0.1)

    def test_drain_episode_sheds_queue_with_hints(self):
        eng = _mk_engine(max_queue=8)
        res = replay_trace(
            eng, _small_trace(5), dt_per_step=0.02,
            episodes=[Episode("drain", at_s=0.2)])
        card = build_scorecard(res)
        assert card["verdict"]["pass"], card["verdict"]
        # everything queued at drain-begin (and every later arrival)
        # sheds as "draining" with a retry hint
        assert card["deterministic"]["shed_by_reason"].get(
            "draining"), card["deterministic"]
        for rec in res.terminal.values():
            if rec["state"] == "shed":
                assert rec.get("retry_after_s") is not None, rec


class TestScorecardUnits:
    def test_shed_reason_typing(self):
        f = sc._shed_reason_type
        assert f("engine is draining") == "draining"
        assert f("displaced by rid 7") == "displaced"
        assert f("slo burn shed") == "slo_burn"
        assert f("queue full (8/8)") == "queue_full"
        assert f("???") == "other"
        assert f(None) == "other"

    def test_jain_index(self):
        assert sc._jain([1.0, 1.0, 1.0]) == 1.0
        assert sc._jain([]) is None
        assert abs(sc._jain([1.0, 0.0]) - 0.5) < 1e-9
        assert sc._jain([0.0, 0.0]) == 1.0

    def test_last_scorecard_lifecycle(self, replay_pair):
        sc.reset()
        assert sc.last_scorecard() is None
        card = build_scorecard(replay_pair[0])
        assert sc.last_scorecard() is card
        sc.reset()
        assert sc.last_scorecard() is None


@pytest.mark.serving
class TestScorecardRoute:
    @pytest.fixture
    def mon(self):
        from paddle_tpu import monitor
        from paddle_tpu.monitor import server
        monitor.reset()
        server.stop_server()
        pt.set_flags({"FLAGS_enable_monitor": True})
        yield monitor
        server.stop_server()
        pt.set_flags({"FLAGS_enable_monitor": False,
                      "FLAGS_enable_monitor_server": False})
        monitor.reset()

    @staticmethod
    def _get(url):
        try:
            with urllib.request.urlopen(url, timeout=10) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def test_scorecard_route(self, mon, replay_pair):
        from paddle_tpu.monitor import server
        sc.reset()
        srv = server.start_server(port=0)
        code, body = self._get(f"{srv.url}/scorecard")
        assert code == 404
        assert json.loads(body)["available"] is False
        card = build_scorecard(replay_pair[0])
        code, body = self._get(f"{srv.url}/scorecard")
        assert code == 200
        served = json.loads(body)
        assert served["verdict"] == card["verdict"]
        assert served["deterministic"]["trace"]["sha256"] \
            == card["deterministic"]["trace"]["sha256"]
        code, body = self._get(f"{srv.url}/")
        assert "/scorecard" in json.loads(body)["routes"]

    def test_replay_metrics_counted(self, mon):
        res = _one_replay()
        snap = mon.snapshot()["counters"]
        assert snap.get("loadgen.replay.offered") == res.offered
        assert snap.get("loadgen.replay.completed") \
            == res.terminal_counts().get("completed")
        assert snap.get("loadgen.replay.shed") \
            == res.terminal_counts().get("shed")
        assert snap.get("loadgen.replay.tokens.useful") \
            == res.useful_tokens()
        build_scorecard(res)
        assert mon.snapshot()["counters"].get(
            "loadgen.scorecard.builds") == 1


# ---------------------------------------------------------------------------
# fleet replay
# ---------------------------------------------------------------------------

@pytest.mark.serving
class TestFleetReplay:
    def test_fleet_replay_two_replicas(self):
        # the fast fleet case tier-1 keeps: 2 replicas, local frames,
        # no kill — every request terminal, none lost, per-replica
        # stats and routing visible
        from paddle_tpu.monitor import federation as fed
        fed.reset()
        try:
            res = replay_fleet(lambda name: _mk_engine(),
                               _small_trace(31), replicas=2,
                               dt_per_tick=0.05, steps_per_tick=2)
            card = build_scorecard(res)
            assert card["verdict"]["pass"], card["verdict"]
            assert len(res.terminal) == len(res.trace.requests)
            assert res.terminal_counts().get("lost", 0) == 0
            assert set(res.engine_stats) == {"replica0", "replica1"}
            replicas_used = {r.get("replica")
                             for r in res.terminal.values()}
            assert replicas_used == {"replica0", "replica1"}
            assert res.fleet_events is not None
        finally:
            fed.reset()

    @pytest.mark.slow
    def test_fleet_kill_episode_recovers(self, tmp_path):
        # scripted replica kill through the fault-injection point: the
        # victim stops stepping, its heartbeat goes stale, the elastic
        # controller replaces it, its in-flight work is typed ``lost``
        # — and the scorecard still passes (the loss is scripted) with
        # a measured recovery_s
        from paddle_tpu.monitor import federation as fed
        fed.reset()
        try:
            trace = generate_trace(
                41, duration_s=1.2, rate=24.0,
                tenants=[TenantSpec("t0"), TenantSpec("t1")],
                prompt_len=(3, 8), max_new_tokens=(4, 12))
            res = replay_fleet(
                lambda name: _mk_engine(), trace, replicas=2,
                episodes=[Episode("kill", at_s=0.3,
                                  replica="replica1")],
                dt_per_tick=0.02, steps_per_tick=1,
                # generous vs CPU compile ticks: a healthy replica's
                # beat refreshes per tick, and a tick (even a fresh
                # replica's compile tick) stays well under this — only
                # the killed victim, which stops stepping entirely,
                # ever goes stale
                heartbeat_dir=str(tmp_path), heartbeat_timeout=6.0)
            kinds = [e["kind"] for e in res.episodes]
            assert "killed" in kinds, res.episodes
            assert "recovered" in kinds, res.episodes
            # the controller spawned a replacement beyond the initial 2
            assert len(res.engine_stats) >= 3, sorted(res.engine_stats)
            card = build_scorecard(res)
            assert card["verdict"]["pass"], card["verdict"]
            assert card["timing"]["recovery_s"] is not None
            assert card["timing"]["recovery_s"] >= 0
            # every submission still accounted in exactly one state
            assert len(res.terminal) == res.offered
            lost = [r for r in res.terminal.values()
                    if r["state"] == "lost"]
            for rec in lost:
                assert rec.get("replica") == "replica1", rec
        finally:
            fed.reset()

    @pytest.mark.slow
    def test_fleet_kill_failover_exactly_once(self, tmp_path):
        # the same scripted kill with FLAGS_serving_failover on: the
        # victim's journaled in-flight work is stranded, re-dispatched
        # through normal admission on a survivor, and ends in exactly
        # one terminal state with lineage — ZERO ``lost``, no token
        # delivered twice (token conservation holds even though the
        # victim's partial generation died with it)
        from paddle_tpu.monitor import federation as fed
        fed.reset()
        try:
            trace = generate_trace(
                41, duration_s=1.2, rate=24.0,
                tenants=[TenantSpec("t0"), TenantSpec("t1")],
                prompt_len=(3, 8), max_new_tokens=(4, 12))
            res = replay_fleet(
                lambda name: _mk_engine(failover=True), trace,
                replicas=2,
                episodes=[Episode("kill", at_s=0.3,
                                  replica="replica1")],
                dt_per_tick=0.02, steps_per_tick=1,
                heartbeat_dir=str(tmp_path), heartbeat_timeout=6.0,
                failover=True)
            kinds = [e["kind"] for e in res.episodes]
            assert "killed" in kinds and "recovered" in kinds
            counts = res.terminal_counts()
            assert counts.get("lost", 0) == 0, counts
            assert len(res.terminal) == res.offered
            # the durability layer saw the strand and settled it
            assert res.failover is not None
            ctr = res.failover["counters"]
            assert ctr["stranded"] >= 1
            assert ctr["redispatched"] + ctr["quarantined"] \
                + ctr["expired"] >= 1
            recovered = [r for r in res.terminal.values()
                         if r.get("recovered_from")]
            assert recovered, res.failover
            for rec in recovered:
                assert rec["recovered_from"] == ["replica1"], rec
                assert rec["state"] in ("completed", "expired",
                                        "shed", "quarantined")
                assert rec.get("failover_attempts", 0) >= 1
            card = build_scorecard(res)
            # token conservation inside the verdict pins "no token
            # delivered twice": emitted == generated - discarded even
            # with the re-dispatch regenerating from scratch
            assert card["verdict"]["pass"], card["verdict"]
            det_fo = card["deterministic"]["failover"]
            assert det_fo["recovered"] == ctr["recovered"]
            assert det_fo["failover_attempts"] >= 1
            t_fo = card["timing"]["failover"]
            assert t_fo["coordinator"]["counters"] == ctr
            if ctr["recovered"]:
                assert t_fo["recovery_s"]["count"] == len(
                    [r for r in res.terminal.values()
                     if r.get("recovery_s") is not None])
                assert t_fo["recovery_s"]["p99"] > 0
        finally:
            fed.reset()

    def test_fleet_flags_off_has_no_failover_surface(self):
        # flag off: no journal, no coordinator, zeroed deterministic
        # block — the flags-off determinism diff is unchanged
        from paddle_tpu.monitor import federation as fed
        fed.reset()
        try:
            res = replay_fleet(lambda name: _mk_engine(),
                               _small_trace(31), replicas=2,
                               dt_per_tick=0.05, steps_per_tick=2)
            assert res.failover is None
            assert res.engine_flags["failover"] is False
            card = build_scorecard(res)
            assert card["deterministic"]["failover"] == {
                "recovered": 0, "failover_attempts": 0,
                "quarantined": 0}
            assert "failover" not in card["timing"]
        finally:
            fed.reset()

    def test_kill_without_heartbeat_rejected(self):
        with pytest.raises(ValueError, match="heartbeat"):
            replay_fleet(lambda name: _mk_engine(), _small_trace(),
                         episodes=[Episode("kill", at_s=0.1)])
