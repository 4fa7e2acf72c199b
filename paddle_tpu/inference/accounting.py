"""What the serving scheduler reports, and the planes that hear it.

``ServingEngine`` (engine.py) schedules. Whatever else wants to know what
happened (counters and gauges, latency histograms, the per-request cost
record, the trace ring's lifecycle instants, the SLO window, the forensics
timeline and decision ring, the failover journal, the federation frame,
``/healthz``, the program registry, the KV numerics sample) hears it here:
the engine raises each event at ONE place, and this module alone knows who
consumes it (docs/serving.md has the table of events, consumers and the
contracts each keeps: the latency windows, the token accounting, the cost
record). It decides for itself, from ``monitor.enabled()`` and from what
is attached (a journal, a frame publisher), whether a call does anything:
with the monitor off and nothing attached every event returns at its first
branch, no plane is called, ``RequestOutput.cost`` is None, and the tokens
are byte-identical either way. Every stamp and tick sits at a seam where a
download has already synchronized the device: the planes add ZERO device
synchronizations at any rate.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..monitor import forensics as _forensics
from ..monitor import server as _mserver
from ..monitor import slo as _slo
from ..monitor import trace as _trace
from ..monitor.registry import LATENCY_BUCKETS_MS as _LATENCY_BUCKETS_MS

__all__ = ["EngineAccounting", "RequestCost"]

# metric names are literals at their call sites throughout this module:
# scripts/check_metrics_docs.py holds every one to docs/observability.md
_COUNT_PREFIX = {
    "evictions": lambda n: _monitor.inc(
        "serving.prefix_cache.evictions", n,
        doc="radix nodes dropped under pool pressure"),
    "lookups": lambda n: _monitor.inc(
        "serving.prefix_cache.lookups", n,
        doc="admission prompt-prefix radix probes"),
    "hits": lambda n: _monitor.inc(
        "serving.prefix_cache.hits", n,
        doc="admissions that forked cached prefix pages"),
    "tokens_saved": lambda n: _monitor.inc(
        "serving.prefix_cache.tokens_saved", n,
        doc="prompt tokens served from cached KV instead of prefill"),
}
_LATENCY_DOCS = {
    "serving.latency.queue_wait_ms":
        "enqueue (or preemption re-queue) to admission",
    "serving.latency.e2e_ms":
        "request lifetime: original enqueue to retirement",
    "serving.latency.ttft_ms":
        "original enqueue to the prefill-sampled first token the client "
        "keeps",
    "serving.latency.tpot_ms": "mean time per output token after the first",
}
# a registered program's name, from the arguments after its kind in the key
_PROGRAM_NAMES = {
    "serving.prefill": lambda g, s, sampled: f"[g{g},s{s}]",
    "serving.prefill_shared": lambda g, s, ncp, sampled:
        f"[g{g},s{s},ctx{ncp}]",
    "serving.decode_chunk": lambda c, sampled:
        f"[c{c}{',sampled' if sampled else ''}]",
    "serving.spec_chunk": lambda c: f"[c{c}]",
}


def _observe_latency(name: str, ms: float):
    _monitor.observe(name, ms, doc=_LATENCY_DOCS[name],
                     buckets=_LATENCY_BUCKETS_MS)


def _tenant_of(req) -> str:
    return getattr(req, "tenant", "default") or "default"


def _engine_health_provider(ref):
    """``/healthz`` contributor over a weakly-held engine: queue depth,
    slot occupancy, page-pool pressure. Returns None once the engine is
    garbage-collected (the server prunes the entry). Always ``ok`` —
    a deep queue is backpressure, not a liveness failure."""
    def provide():
        eng = ref()
        if eng is None:
            return None
        return {
            "ok": True,
            "queue_depth": len(eng.queue),
            "slots_live": sum(1 for s in eng.slots if s is not None),
            "num_slots": eng.num_slots,
            "pages_free": eng.cache.alloc.free_pages,
            "pages_total": eng.cache.num_pages,
            "requests_completed": eng.stats.completed,
        }
    return provide


@dataclasses.dataclass
class RequestCost:
    """Per-request resource attribution, accumulated at the engine's
    existing host-sync seams (monitor-gated; see the module
    docstring). Cumulative across preemption re-queues — the record
    follows the REQUEST, not one run of it."""

    tenant: str = "default"
    priority: int = 0
    prefill_tokens: int = 0      # prompt tokens prefilled (re-prefills
    #                              after preemption included; tokens a
    #                              cached prefix skipped are NOT here —
    #                              they were not work done)
    prefix_cached_tokens: int = 0    # prompt tokens served from the
    #                              radix prefix cache instead of
    #                              prefill (cumulative across re-runs)
    prefill_flops_saved: float = 0.0  # modeled FLOPs the cached prefix
    #                              skipped (tail program's registered
    #                              per-padded-token rate x cached)
    decode_tokens: int = 0       # decode emissions (work done, incl.
    #                              tokens a preemption later discarded)
    discarded_tokens: int = 0    # thrown away by preemption recompute
    queue_wait_ms: float = 0.0   # SUM of every enqueue->admission wait
    page_seconds: float = 0.0    # KV pages held x wall (chunk edges)
    slot_steps: int = 0          # decode-grid steps a slot was held
    grid_steps: int = 0          # grid capacity (steps x slots) that
    #                              elapsed during the residencies
    slot_share: Optional[float] = None   # slot_steps / grid_steps
    model_flops: float = 0.0     # registered program FLOPs, split
    #                              across the dispatch's live slots
    preemptions: int = 0
    ttft_ms: Optional[float] = None
    tpot_ms: Optional[float] = None
    e2e_ms: Optional[float] = None

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class _Record:
    """One request's accounting state from submit to its terminal event,
    kept on the request as ``req._acct`` (None with the monitor off;
    this module alone reads and writes it). ``cost`` and ``t0`` follow
    the REQUEST across preemption re-queues (they re-enter the queue via
    appendleft, not submit); ``t_enqueue`` is refreshed by each re-queue
    and anchors queue_wait; the rest belongs to the run in a slot."""

    __slots__ = ("cost", "t0", "t_enqueue", "t_first", "t_last", "t_tick",
                 "steps0")

    def __init__(self, cost: RequestCost, now: float):
        self.cost = cost
        self.t0 = self.t_enqueue = now   # t0 anchors TTFT and e2e
        self.t_first = self.t_last = None    # first / latest token stamps
        self.t_tick = None       # last page-seconds integration stamp;
        #                          not None = a run of it is in a slot
        self.steps0 = 0          # engine decode_steps at admission


class _Run:
    """One dispatched program between ``dispatching`` and what is done
    with its download: the exec sample to close, the registered FLOPs a
    sharer, the stamp of the download."""

    __slots__ = ("exec_rec", "share", "t")

    def __init__(self, exec_rec, share):
        self.exec_rec, self.share, self.t = exec_rec, share, None


class EngineAccounting:
    """Built by a :class:`ServingEngine`, which it holds weakly."""

    def __init__(self, engine):
        self._eng = weakref.proxy(engine)
        # Exactly-once failover (inference/failover.py) and fleet SLO
        # federation (monitor/federation.py): one None check a terminal
        # event / a scheduler step while unattached
        self.journal = None
        self.frame_pub = None
        # KV-page absmax sampling (monitor/numerics.py): 1-in-N decode
        # chunks dispatch a tiny per-layer per-page |K|/|V| max over
        # the pool AFTER the chunk's emitted-grid download has already
        # synchronized the device — zero added block_until_ready calls
        # at any rate (PR 9's pattern, pinned by test)
        self._kv_chunks = 0
        self._kv_absmax_fn = None
        # registered-program FLOPs, cached per registry key: the cost
        # plane reads it once per chunk, not once per slot, and the
        # cached value keeps the per-dispatch cost at one dict lookup
        self._flops_by_key: dict = {}
        # Operator plane: start the telemetry server when its flag is
        # set (one cached branch otherwise) and contribute this
        # engine's scheduler state to /healthz. The provider holds the
        # engine WEAKLY — a retired engine prunes itself, never pins —
        # and registers only while some plane could read it (monitor on
        # or server flag/running): a fully-off process must not grow
        # the provider map one entry per engine, ever.
        # Process-unique uid (GIL-atomic counter, monitor/programs.py)
        # keys both the /healthz provider name ("serving:<n>" — two
        # engines must not evict each other's view) and the
        # introspection-registry records (which outlive the engine —
        # id(self) reuse must not alias a successor onto stale ones).
        _mserver.maybe_start()
        self._uid = _monitor.programs.next_uid()
        if _monitor.enabled() or _mserver.plane_active():
            _mserver.register_health_provider(
                f"serving:{self._uid}",
                _engine_health_provider(weakref.ref(engine)))
        if _monitor.enabled():
            _monitor.set_gauge("serving.pages.total", engine.cache.num_pages,
                               doc="KV page pool capacity")
            # Sharding inspector (distributed/introspect.py): the param
            # tree's per-leaf layout for /sharding — pure serving runs
            # populate the view with no training loop in sight.
            from ..distributed import introspect as _introspect
            _introspect.register_sharded_tree(
                f"serving:{self._uid}.params", engine.params)

    # -- what the scheduler reads back ---------------------------------------

    def burning(self) -> bool:
        """Whether the SLO latency burn alerts (``shed_on_burn``).
        load_only: the sheds this gate produces are availability-bad
        records, and feeding them back would lock best-effort traffic
        out long after the real overload cleared."""
        return _monitor.enabled() and _slo.burn_alerting(load_only=True)

    def autoscale_payload(self) -> dict:
        eng = self._eng
        resident = sum(1 for s in eng.slots if s is not None)
        return _slo.demand_model(len(eng.queue), resident, eng.num_slots,
                                 self._pages_free_fraction())

    def retry_after(self) -> float:
        return _slo.retry_after_hint(self.autoscale_payload())

    def work_done(self, slot) -> int:
        """A live request's accumulated work, for ``slo_preemption``'s
        victim key: prefill + decode tokens of the cost record
        (cumulative across re-runs) when the monitor keeps one, else the
        current run's KV length — the monitor-off proxy of the same
        quantity."""
        rec = getattr(slot.req, "_acct", None)
        if rec is not None and rec.t_tick is not None:
            return rec.cost.prefill_tokens + rec.cost.decode_tokens
        return slot.kv_len

    # -- opt-ins -------------------------------------------------------------

    def publish_frames(self, name, dir_path, **kw):
        from ..monitor import federation as _fed
        self.frame_pub = _fed.FramePublisher(name, dir_path=dir_path, **kw)
        self.frame_pub.maybe_publish(self._eng, force=True)
        return self.frame_pub

    def attach_journal(self, name, dir_path, client):
        from .failover import AdmissionJournal
        self.journal = AdmissionJournal(name, dir_path=dir_path,
                                        client=client)
        return self.journal

    # -- a request's life ----------------------------------------------------

    def submitted(self, req):
        """``req`` passed every gate and is about to be queued. A
        submission always starts afresh (a client may resubmit a request
        it kept: the cost record restarts, TTFT/e2e re-anchor)."""
        req._acct = None
        if _monitor.enabled():
            now = time.perf_counter()
            plen = int(req.prompt.shape[0])
            req._acct = _Record(RequestCost(tenant=req.tenant,
                                            priority=req.priority), now)
            _trace.instant("serving.enqueue", rid=req.rid, prompt=plen,
                           max_new=req.max_new_tokens, tenant=req.tenant)
            _forensics.note(req.rid, "enqueue", t=now, tenant=req.tenant,
                            priority=req.priority, prompt=plen,
                            max_new=req.max_new_tokens)
        if self.journal is not None:
            # journal AFTER every gate that could still refuse the
            # request (a shed/rejected submission never entered the
            # engine and must not be re-dispatched); the engine pinned
            # the sampling key BEFORE this record is written so a
            # re-dispatch replays byte-identical tokens
            self.journal.admit(req)

    def deferred(self, req, reason: str, **inputs):
        """One admission-scan deferral (forensics timeline + decision
        ring, both self-gated and coalescing — a head request blocked
        on the same reason for many steps is ONE record with a count,
        not a flood)."""
        if _monitor.enabled():
            _forensics.note_defer(req.rid, reason, **inputs)
            _forensics.decision("defer", rid=req.rid, reason=reason,
                                **inputs)

    def displaced(self, victim, by, max_queue: int):
        if _monitor.enabled():
            _forensics.decision(
                "displace", rid=victim.rid, reason="queue_full",
                queue_depth=len(self._eng.queue) + 1, max_queue=max_queue,
                by_rid=by.rid, by_priority=by.priority,
                victim_priority=getattr(victim, "priority", 0))

    def prefix(self, event: str, n: int = 1):
        if _monitor.enabled():
            _COUNT_PREFIX[event](n)

    def admitted(self, group, s_pad: int, cached: int, free_slots: int):
        """A prefill group leaves the queue for its slots: ``cached``
        prompt tokens of each come from the prefix cache, the rest is
        prefilled. Page-seconds integrate from here (the pages were
        allocated by the admission scan) at chunk-edge resolution."""
        if not _monitor.enabled():
            return
        eng, now = self._eng, time.perf_counter()
        _forensics.decision(
            "admit", rid=group[0].rid, group=len(group), bucket=s_pad,
            free_slots=free_slots, queue_depth=len(eng.queue),
            pfx_cached=cached)
        for r in group:
            tail = int(np.asarray(r.prompt).shape[0]) - cached
            # the prefill-sampled first token counts here so the
            # counter agrees with stats.tokens_generated
            _monitor.inc("serving.requests.admitted")
            _monitor.inc("serving.tokens.generated")
            _monitor.inc("serving.tokens.prefilled", tail)
            rec, wait_ms = getattr(r, "_acct", None), None
            if rec is not None:
                wait_ms = (now - rec.t_enqueue) * 1e3
                _observe_latency("serving.latency.queue_wait_ms", wait_ms)
                # CUMULATIVE across preemption re-queues: the histogram
                # above observes each wait once; the record answers
                # "how long did this request spend queued in total"
                rec.cost.queue_wait_ms += wait_ms
                rec.cost.prefill_tokens += tail
                rec.cost.prefix_cached_tokens += cached
                rec.t_tick, rec.steps0 = now, eng.stats.decode_steps
            _trace.instant("serving.admit", rid=r.rid)
            # the admit event carries the prefix-cache match result
            # (cached prefix length this group was grouped on)
            _forensics.note(
                r.rid, "admit", t=now, bucket=s_pad, group=len(group),
                wait_ms=round(wait_ms, 3) if wait_ms is not None else None,
                pfx_cached=cached)

    def first_tokens(self, run, group, s_eff: int, cached: int):
        """The group's first tokens are on the host. TTFT is NOT
        observed here: a preemption would discard this run's tokens and
        re-prefill, double-sampling the histogram with a first token the
        client never saw; the record carries ``t_first`` to the terminal
        event, which observes once per completed request. The lifecycle
        instant still marks every prefill (preempted runs included)."""
        if not _monitor.enabled():
            return
        now = time.perf_counter()
        share = run.share if run is not None else None
        for r in group:
            _trace.instant("serving.first_token", rid=r.rid)
            _forensics.note(r.rid, "first_token", t=now)
            rec = getattr(r, "_acct", None)
            if rec is None:
                continue
            rec.t_first = rec.t_last = now
            if share:
                rec.cost.model_flops += share
                # modeled: the tail program's per-padded-token cost
                # scaled by the tokens the cache served — what a full
                # prefill would have added, to first order
                rec.cost.prefill_flops_saved += share / s_eff * cached

    def preempted(self, slot, idx: int, policy: str):
        """``slot``'s request goes back to the queue's front; called
        BEFORE its pages are freed — an evicted request PAID for the
        pages it held even though the work is recomputed."""
        if not _monitor.enabled():
            return
        _monitor.inc("serving.requests.preempted")
        _monitor.inc("serving.tokens.discarded", slot.gen,
                     doc="sampled tokens thrown away by preemption "
                         "recompute")
        req, now = slot.req, time.perf_counter()
        work = self.work_done(slot)
        rec = getattr(req, "_acct", None)
        if rec is not None:
            if rec.t_tick is not None:
                self._leaves_slot(rec, req.rid, now)
                rec.cost.discarded_tokens += slot.gen
            # the re-queue refreshes t_enqueue: the NEXT wait
            # accumulates onto the record's cumulative queue_wait_ms at
            # re-admission
            rec.t_enqueue = now
        tenant = _tenant_of(req)
        _trace.instant("serving.preempt", rid=req.rid, discarded=slot.gen,
                       tenant=tenant)
        # the victim-selection inputs that chose this slot, recorded so
        # the eviction is auditable (forensics decision ring + the
        # victim's own timeline)
        victim = dict(policy=policy, slot=idx,
                      priority=getattr(req, "priority", 0),
                      prior_preemptions=slot.preemptions, work=int(work))
        _forensics.decision("preempt", rid=req.rid, discarded=slot.gen,
                            **victim)
        _forensics.note(req.rid, "preempt", t=now, tenant=tenant,
                        discarded=slot.gen, **victim)

    def finished(self, req, slot, idx, state: str, reason, hint,
                 entered: bool) -> Optional[RequestCost]:
        """The ONE terminal event of a request: ``state`` is completed,
        expired (``slot`` None: in the queue), shed (``entered`` False:
        refused at submit, else out of the queue) or rejected. Called
        BEFORE the slot's pages are freed (the final page-seconds tick
        reads them). Returns the cost record for ``RequestOutput``."""
        eng, rid = self._eng, req.rid
        if entered and self.journal is not None:
            # the completion marker lands BEFORE the output can be
            # harvested: a crash after this point re-dispatches
            # nothing for this rid (exactly-once dedup)
            self.journal.finish(
                rid, state, tokens=len(slot.tokens) if slot else 0)
        if not _monitor.enabled():
            return None
        now = time.perf_counter()
        if state == "rejected":
            _monitor.inc("serving.requests.rejected",
                         doc="malformed submissions refused at the door "
                             "(engine state untouched)")
            # availability = non-rejected fraction: the refusal must
            # enter the SLO window, attributed to whatever tenant the
            # submission claimed (best-effort — the rejection may be
            # ABOUT the tenant field)
            try:
                tenant = str(req.tenant or "default")[:128] or "default"
            except Exception:
                tenant = "default"
            _trace.instant("serving.reject", rid=rid, reason=reason)
            _slo.record_rejected(tenant)
            _forensics.note_terminal(rid, "rejected", reason=reason,
                                     tenant=tenant)
            return None
        tenant = _tenant_of(req)
        rec = getattr(req, "_acct", None) if entered else None
        cost = rec.cost if rec is not None else None
        if cost is not None:
            if slot is None:
                cost.queue_wait_ms += (now - rec.t_enqueue) * 1e3
            else:
                self._leaves_slot(rec, rid, now)
        if state == "shed":
            _monitor.inc("serving.requests.shed",
                         doc="admissible work refused by overload policy "
                             "(bounded queue, SLO burn, displacement, "
                             "drain) with a retry_after_s hint")
            if cost is not None:
                # the shed rides availability like a rejection, but its
                # consumption (prefill before a preemption, page-seconds,
                # the queue wait above) folds into the tenant
                # aggregates — the tenant PAID for it
                _slo.record_request(dict(cost.as_dict(), rejected=True,
                                         shed=True))
            else:
                _slo.record_shed(tenant)
            _trace.instant("serving.shed", rid=rid, reason=reason,
                           retry_after_s=hint, tenant=tenant)
            where = dict(queued=True) if entered \
                else dict(queue_depth=len(eng.queue))
            _forensics.decision("shed", rid=rid, reason=reason,
                                priority=getattr(req, "priority", 0),
                                draining=eng.draining, **where)
            _forensics.note_terminal(rid, "shed", reason=reason,
                                     tenant=tenant,
                                     retry_after_s=round(hint, 3))
            return cost
        preemptions = slot.preemptions if slot is not None \
            else getattr(req, "_preempt_count", 0)
        if cost is not None:
            cost.preemptions = preemptions
            cost.e2e_ms = (now - rec.t0) * 1e3
            if cost.grid_steps > 0:
                cost.slot_share = round(cost.slot_steps / cost.grid_steps, 6)
        e2e = cost.e2e_ms if cost is not None else None
        if state == "expired":
            _monitor.inc("serving.requests.expired",
                         doc="requests retired by their submit-time "
                             "deadline (expired in queue or evicted from "
                             "the running batch)")
            n = len(slot.tokens) if slot is not None else 0
            if cost is not None:
                # the SLO window counts an expiry BAD for availability and
                # excludes it from the latency objectives (monitor/slo.py)
                _slo.record_request(dict(cost.as_dict(), expired=True))
            _trace.instant("serving.expire", rid=rid, tokens=n,
                           in_slot=slot is not None, tenant=tenant)
            if slot is not None:
                _forensics.decision("evict", rid=rid, reason="deadline",
                                    slot=idx, tokens=n)
            _forensics.note_terminal(rid, "expired", t=now, e2e_ms=e2e or None,
                                     tenant=tenant, tokens=n,
                                     in_slot=slot is not None)
            return cost
        _monitor.inc("serving.requests.completed")
        if cost is not None:
            _observe_latency("serving.latency.e2e_ms", e2e)
            if rec.t_first is not None:
                # observed at retirement, not at prefill: a preempted
                # request re-prefills, and only the surviving run's first
                # token — the one the client keeps — counts
                cost.ttft_ms = (rec.t_first - rec.t0) * 1e3
                _observe_latency("serving.latency.ttft_ms", cost.ttft_ms)
                if slot.gen > 1 and rec.t_last is not None:
                    # mean inter-token time over the decode phase; t_last
                    # is the arrival of the final emitted token (chunk-edge
                    # resolution), t_first the prefill-sampled token
                    cost.tpot_ms = ((rec.t_last - rec.t_first)
                                    / (slot.gen - 1) * 1e3)
                    _observe_latency("serving.latency.tpot_ms", cost.tpot_ms)
            _slo.record_request(cost.as_dict())
        _trace.instant("serving.retire", rid=rid, tokens=slot.gen,
                       preemptions=preemptions, tenant=tenant)
        _forensics.note_terminal(
            rid, "completed", t=now, e2e_ms=e2e or None,
            ttft_ms=(cost.ttft_ms or None) if cost is not None else None,
            tenant=tenant, tokens=slot.gen, preemptions=preemptions)
        return cost

    def _leaves_slot(self, rec, rid, now: float):
        """A run's residency ends (retired, evicted by its deadline, or
        preempted), BEFORE its pages are freed: the final page-seconds
        tick, pages held from the last chunk edge until now, and the
        decode grid's capacity (steps x slots) that elapsed during the
        residency — ``slot_share`` is the request's steps over it,
        cumulative across preemption re-runs, None when it retired
        without a decode chunk in between."""
        eng = self._eng
        if rec.t_tick is not None:
            rec.cost.page_seconds += (eng.cache.alloc.page_count(rid)
                                      * (now - rec.t_tick))
        rec.cost.grid_steps += (eng.stats.decode_steps
                                - rec.steps0) * eng.num_slots
        rec.t_tick = None

    def _pages_free_fraction(self) -> float:
        cache = self._eng.cache
        return cache.alloc.free_pages / cache.num_pages \
            if cache.num_pages else 0.0

    # -- a scheduler step ----------------------------------------------------

    def tick(self, live: int, pages_in_use: int):
        """Once a scheduler step, after admission."""
        eng = self._eng
        if _monitor.enabled():
            _monitor.set_gauge("serving.queue.depth", len(eng.queue),
                               doc="requests waiting for admission")
            _monitor.set_gauge("serving.pages.in_use", pages_in_use,
                               doc="KV pages currently allocated")
            # autoscale feed (monitor/slo.py): one host tick per
            # scheduling step — queue depth, live slots, page slack.
            # The gauges themselves are recomputed at scrape time.
            _slo.note_sched_tick(len(eng.queue), live, eng.num_slots,
                                 self._pages_free_fraction())
        if self.frame_pub is not None:
            # federation frame on the same host tick (rate-limited
            # inside; pure host state — zero device syncs)
            self.frame_pub.maybe_publish(eng)

    def drain_begun(self, again: bool):
        if _monitor.enabled():
            _trace.instant("serving.drain.begin",
                           queued=len(self._eng.queue), again=again)

    def drain_queue_shed(self, again: bool):
        if self.frame_pub is not None:
            # drain state must reach the federation controller now,
            # not a rate-limit later — but only the TRANSITION forces:
            # the controller re-invokes begin_drain every retry tick
            # of a slow drain, and forcing each call would bypass the
            # rate limit into per-tick transport I/O
            self.frame_pub.maybe_publish(self._eng, force=not again)

    def dispatching(self, spec_key, jitted, args, kwargs, donated,
                    sharers: int):
        """A prefill, decode chunk or verify window is about to be
        dispatched (BEFORE: the call donates the pool buffers): register
        the program once a specialization, open an exec sample, and
        split its registered cost-analysis FLOPs across the ``sharers``
        (the real requests of a group, dummy pad rows attribute nowhere;
        the live slots of a chunk: done/empty slots ride along for free
        in the static grid, the work exists because of the live ones).
        None / 0 FLOPs when the backend never reported: skipped, not
        fabricated. Returns what ``downloaded`` takes, None when off."""
        if not _monitor.enabled():
            return None
        key = self._record_program(spec_key, jitted, args, kwargs, donated)
        from ..monitor import exectime as _exectime
        flops = self._program_flops(key)
        return _Run(_exectime.maybe_sample(key, feed_last=False),
                    flops / sharers if flops else None)

    def downloaded(self, run, kv_sample: bool = True):
        """The program's result reached the host. That download already
        synchronized the device: closing the exec sample with rec(None)
        and the numerics sample add ZERO extra block_until_ready calls
        of in-flight work at this seam."""
        if run is not None:
            if run.exec_rec is not None:
                run.exec_rec(None)
            run.t = time.perf_counter()
        if kv_sample and _monitor.enabled():
            self._maybe_sample_kv_absmax()

    def chunk_done(self, run, slots, C: int, emitted, accepted=None):
        """A decode chunk or verify window of ``C`` steps is on the host
        and given out: ``slots`` the live slots, ``emitted`` how many
        tokens each got, ``accepted`` (a verify window) how many drafts
        each had confirmed. Cost attribution at the chunk edge the
        download already synchronized: pure host reads (allocator page
        counts, the cached program FLOPs)."""
        if not _monitor.enabled():
            return
        _monitor.set_gauge("serving.batch.occupancy",
                           round(self._eng.stats.occupancy(), 4),
                           doc="generated tokens / (decode steps x slots)")
        _monitor.inc("serving.tokens.generated", sum(emitted))
        if accepted is not None:
            _monitor.inc("serving.spec.rounds", len(slots),
                         doc="per-sequence speculative verify rounds")
            _monitor.inc("serving.spec.drafted", (C - 1) * len(slots),
                         doc="n-gram draft tokens proposed for verification")
            _monitor.inc("serving.spec.accepted", sum(accepted),
                         doc="draft tokens confirmed by the greedy verify")
        t = run.t if run is not None and run.t is not None \
            else time.perf_counter()
        share = run.share if run is not None else None
        page_count = self._eng.cache.alloc.page_count
        for j, (s, n) in enumerate(zip(slots, emitted)):
            if accepted is not None:
                # aggregate fold, no event append: spec rounds are
                # per-chunk-rate and would flood the bounded timeline
                _forensics.note_spec(s.req.rid, C - 1, accepted[j])
            rec = getattr(s.req, "_acct", None)
            if rec is None or rec.t_tick is None:
                continue
            if n:
                rec.t_last = t
            rec.cost.page_seconds += page_count(s.req.rid) * (t - rec.t_tick)
            rec.t_tick = t
            rec.cost.slot_steps += C
            rec.cost.decode_tokens += n
            if share:
                rec.cost.model_flops += share

    # -- the planes' own helpers ---------------------------------------------

    def _record_program(self, spec_key, jitted, args, kwargs, donated):
        """Register a serving program with the introspection registry
        (monitor/programs.py) once per specialization — signature,
        donation map, cost-analysis FLOPs (one re-trace), and a lazy
        memory analyzer the ``/programs`` endpoint resolves. The
        registry ITSELF is the dedup (not an engine-local set): after
        a ``monitor.reset()`` mid-run the next dispatch re-registers,
        so the scrape endpoints and the headroom estimate's temp
        reservation recover instead of staying empty forever. The
        per-dispatch cost after the first is one locked dict lookup,
        monitor-on only. The params sharding tree rides the same
        reset-recovery seam (ensure_sharded_tree)."""
        from ..distributed import introspect as _introspect
        from ..monitor import programs as _programs
        eng = self._eng
        _introspect.ensure_sharded_tree(
            f"serving:{self._uid}.params", lambda: eng.params)
        key = ("engine", self._uid) + spec_key
        if _programs.has_record(key):
            _programs.note_hit(key)
            return key
        _programs.record_jit_call(
            key, spec_key[0] + _PROGRAM_NAMES[spec_key[0]](*spec_key[1:]),
            jitted, args, kwargs=kwargs, source="serving", donated=donated)
        return key

    def _program_flops(self, key):
        """Cached ``monitor/programs.flops_of`` read (None when the
        backend never reported a count). An unknown key is NOT cached
        as None: a ``monitor.reset()`` mid-run re-registers on the
        next dispatch and the lookup must recover with it."""
        v = self._flops_by_key.get(key)
        if v is None:
            from ..monitor import programs as _programs
            v = _programs.flops_of(key)
            if v is not None:
                self._flops_by_key[key] = v
        return v

    def _maybe_sample_kv_absmax(self):
        """KV-page absmax distribution feed (numerics plane): every
        1-in-N chunks (``PADDLE_TPU_KV_SAMPLE``; 0 disables) compute
        per-layer per-page max|K| / max|V| over the pool, keep only
        the pages the allocator holds live (free pages are zeros that
        would drown the distribution), and record them. Runs right
        after the chunk's token download — the device is idle, so the
        small [L, P] compute + transfer rides the existing seam with
        zero extra synchronizations of in-flight work."""
        from ..monitor import numerics as _numerics
        rate = _numerics.kv_sample_rate()
        if rate <= 0:
            return
        self._kv_chunks += 1
        if self._kv_chunks < rate:
            return
        self._kv_chunks = 0
        cache, quant = self._eng.cache, self._eng._kv_quant
        in_use = np.flatnonzero(cache.alloc._ref > 0)
        if in_use.size == 0:
            return
        if self._kv_absmax_fn is None:
            if quant:
                # quantized pool: codes [L, P, kv, page, hd] + scales
                # [L, P, kv]. absmax = max|code|·scale; also surface the
                # quantizer's own health — the scale magnitudes and the
                # fraction of codes pinned at the clip rail (±127)
                def _q_absmax(k, v):
                    def one(leaf):
                        am = jnp.max(jnp.abs(leaf["q"]), axis=(3, 4))
                        return jnp.max(am.astype(jnp.float32)
                                       * leaf["s"], axis=2)
                    clip = (
                        jnp.mean((jnp.abs(k["q"]) == 127),
                                 axis=(0, 2, 3, 4)).astype(jnp.float32)
                        + jnp.mean((jnp.abs(v["q"]) == 127),
                                   axis=(0, 2, 3, 4)).astype(jnp.float32)
                    ) * 0.5                               # [P]
                    scales = jnp.maximum(jnp.max(k["s"], axis=2),
                                         jnp.max(v["s"], axis=2))
                    return one(k), one(v), scales, clip
                self._kv_absmax_fn = jax.jit(_q_absmax)
            else:
                # pool layout [L, P, kv, page, hd] -> per-layer per-page
                self._kv_absmax_fn = jax.jit(
                    lambda k, v: (
                        jnp.max(jnp.abs(k), axis=(2, 3, 4)
                                ).astype(jnp.float32),
                        jnp.max(jnp.abs(v), axis=(2, 3, 4)
                                ).astype(jnp.float32)))
        out = self._kv_absmax_fn(cache.pool["k"], cache.pool["v"])
        km = np.asarray(out[0])[:, in_use]
        vm = np.asarray(out[1])[:, in_use]
        _numerics.record_kv_absmax(km, vm)
        if quant:
            scales = np.asarray(out[2])[:, in_use]
            clip = float(np.mean(np.asarray(out[3])[in_use]))
            _numerics.record_kv_quant(scales, clip)
