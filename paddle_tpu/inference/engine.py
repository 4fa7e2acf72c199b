"""Continuous-batching serving engine over the paged KV cache.

Reference capability: the vLLM/TGI scheduler loop (and the reference's
fastdeploy serving stack) — a request queue feeding a fixed grid of
decode slots, admission gated on free KV pages, prefill-then-join so a
new request enters the running batch without draining it, retirement
freeing pages the moment a sequence finishes — rebuilt TPU-native:

- The decode data plane is ONE jitted program over the static
  ``[num_slots]`` grid (cache_decode_step + vectorised sampling inside
  a ``lax.scan`` of ``decode_chunk`` steps), so continuous batching
  never retraces: joins/retires only permute host-side block tables
  between chunks. One device round-trip per chunk, not per token. Every
  program takes and returns the cache WHOLE, one donated pytree: the two
  page pools and, for a family that keeps one, the recurrent state a
  sequence, which slots reach through a row table uploaded with the
  block tables.
- Admission policy: a request is admitted when a slot is free AND the
  pool keeps >= ``watermark`` free pages after its prompt allocation —
  the page headroom that lets RUNNING requests keep appending without
  immediate preemption.
- Preemption: when a running request cannot get its next page, the
  youngest running request is evicted (pages freed, request requeued
  for full recomputation — the vLLM "recompute" policy, the right
  choice when sequences are short relative to prefill cost).
- Per-step slot compaction: retirements compact the active slots to the
  low indices before each admission pass, so occupancy accounting and
  the admission scan touch a dense prefix.

Instrumentation (paddle_tpu.monitor, FLAGS_enable_monitor-gated):
``serving.pages.in_use|total``, ``serving.batch.occupancy``,
``serving.queue.depth`` gauges; ``serving.requests.admitted|completed|
preempted``, ``serving.tokens.generated|prefilled|discarded`` counters.
The same numbers are always available unconditionally on
``engine.stats``.

SLO latency (monitor-gated, one cached-flag branch when off): each
request's lifecycle is stamped enqueue -> admit -> prefill -> first
token -> retire, feeding the ``serving.latency.*`` histograms —
``queue_wait_ms`` (latest enqueue to admission; a preempted request
re-queues and waits again — each wait observed once, while the
per-request cost record keeps the CUMULATIVE sum), ``ttft_ms``
(ORIGINAL enqueue to the
prefill-sampled first token of the run the client KEEPS — observed
once per request at retirement, so a preempted run's discarded first
token never biases the histogram),
``tpot_ms`` (mean inter-token time over the decode phase, chunk-edge
resolution), ``e2e_ms`` (original enqueue to retire). All carry
bucket-interpolated p50/p90/p95/p99 in their snapshots. The same
milestones land in the ``monitor.trace`` ring as lifecycle events, so
a flight record shows which requests were in flight at a crash.

Spans (always on; ``monitor.trace.span``): every phase of ``step()`` —
expire, retire, compact, admit with each group's prefill, the page
reservation, the decode or verify chunk — and inside prefill and chunk
the host's work apart from its waiting (``.build``, ``.dispatch``,
``.fetch``, ``.emit``; a program's first call under
``serving.compile``) is a span under the prefix ``serving.`` (the tree
is in ``ServingEngine.step``'s docstring). In the ring they ride the
monitor flag like everything above; as ``jax.profiler`` annotations
they are in ANY open profiler session (``/profile``, a benchmark's, a
user's ``start_trace``) on the device trace's clock, so a device idle
gap reads as what the host was doing. With no session a span is a
no-op of about a microsecond, ten to fifteen a step. Add one only at a
boundary between layers or between host work and waiting, never inside
a loop over slots. The device programs are named to match:
``jit_decode_chunk``, ``jit_spec_verify``, ``jit__pf``,
``jit__join_first``.

Token accounting contract (pinned by tests/test_trace.py):
``serving.tokens.generated`` counts every SAMPLED token (prefill's
first token + decode emissions — work done, including work later
thrown away); ``serving.tokens.discarded`` counts tokens a preemption
discarded for recompute. On a drained engine
``generated - discarded == sum(len(output.tokens))`` exactly.

Cost attribution (monitor-gated, PR 12): requests carry a ``tenant``
(default ``"default"``) and ``priority``, validated/coerced at submit
with the rest of the isolation screening, and every request
accumulates a :class:`RequestCost` record across its lifecycle —
prefill/decode/discarded tokens, CUMULATIVE queue wait across
preemption re-queues (the ``queue_wait_ms`` histogram still observes
each individual wait once), page-seconds (pages held x wall,
integrated at the chunk boundaries the emitted-grid download already
synchronizes — the cost plane adds ZERO device synchronizations at
any rate), slot steps + occupancy share, and modeled FLOPs (the
chunk/prefill program's registered cost-analysis FLOPs from
``monitor/programs.py``, split evenly across the live slots/group
rows that shared the dispatch). The record rides out on
``RequestOutput.cost`` and folds into ``monitor/slo.py``'s windowed
SLO accounting + bounded per-tenant aggregates at retirement; each
scheduler step also feeds the autoscale tick
(``slo.note_sched_tick``). Monitor off: ``cost`` is None and none of
this exists — byte-identical emitted tokens either way.

Overload control (PR 13, the ACTING half of ROADMAP item 5 — all
flag-gated, every flag default OFF, flags-off scheduling byte-identical
to the accounting-only engine; see docs/overload.md):

- **Priority admission** (``FLAGS_serving_priority_admission``): the
  admission scan orders the queue by (priority desc, arrival) and
  enforces ``FLAGS_serving_tenant_inflight_cap`` live slots per tenant.
- **Bounded queue + shedding** (``FLAGS_serving_max_queue``,
  ``FLAGS_serving_shed_on_burn``): a full queue — or an SLO
  fast-burn, for priority<=0 work — sheds submissions with a typed
  :class:`EngineOverloaded` carrying a ``retry_after_s`` hint from the
  autoscale demand model; a higher-priority arrival displaces the
  lowest-priority queued request instead.
- **Deadlines** (per-request ``Request.deadline_s``, default off):
  a spent TTL expires the request in queue or evicts it from the
  running batch (partial tokens delivered, ``finish_reason="expired"``,
  cost recorded).
- **SLO-aware preemption** (``FLAGS_serving_slo_preemption``): page
  pressure evicts the lowest-(priority, prior preemptions, accumulated
  work) request instead of youngest-first.
- **Drain lifecycle** (:meth:`ServingEngine.begin_drain`): stop
  admitting, shed the queue with retry hints, finish live decodes;
  ``drain_complete`` gates the elastic controller's scale-in
  (``distributed/fleet/elastic.py``).

Every submitted request ends in exactly one of completed / rejected /
expired / shed, with a typed reason — nothing is dropped silently.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..core import enforce as E
from ..monitor import server as _mserver
from ..monitor import trace as _trace
from ..monitor import slo as _slo
from ..monitor import forensics as _forensics
from ..monitor.registry import LATENCY_BUCKETS_MS as _LATENCY_BUCKETS_MS
from .paged import (PagedKVCache, PrefixCache, cache_decode_step,
                    cache_prefill, cache_prefill_shared,
                    cache_verify_window)

_NO_SPAN = contextlib.nullcontext()     # what _first_call gives after the first


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

def _observe_latency(name: str, ms: float, doc: str):
    _monitor.observe(name, ms, doc=doc, buckets=_LATENCY_BUCKETS_MS)

__all__ = ["EngineOverloaded", "Request", "RequestCost", "RequestOutput",
           "RequestRejected", "ServingEngine"]


class RequestRejected(E.InvalidArgumentError):
    """A malformed submission, refused at the door.

    Raised by :meth:`ServingEngine.submit` BEFORE the request touches
    the queue, the page pool, or any device state — so one client's
    garbage (oversized prompt, empty prompt, non-finite temperature,
    out-of-vocab token ids) can never detonate mid-chunk and take down
    the engine loop for every other in-flight request. Counted under
    ``serving.requests.rejected``. Subclasses the framework's
    InvalidArgumentError (and therefore ValueError), so existing typed
    handlers keep working."""

    def __init__(self, rid, reason: str):
        self.rid = rid
        self.reason = reason
        super().__init__(f"request {rid!r} rejected: {reason}")


class EngineOverloaded(RequestRejected):
    """Backpressure: a WELL-FORMED submission refused by overload
    policy — bounded queue full (``FLAGS_serving_max_queue``), SLO
    fast-burn shedding (``FLAGS_serving_shed_on_burn``), or a draining
    replica. Unlike its malformed-submission parent this is not the
    client's fault: ``retry_after_s`` carries a hint computed from the
    autoscale demand model (``monitor/slo.retry_after_hint`` over this
    engine's own state), so the caller can back off or retry on
    another replica. Counted under ``serving.requests.shed``."""

    def __init__(self, rid, reason: str, retry_after_s: float):
        self.retry_after_s = retry_after_s
        super().__init__(rid, reason)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # [S] int32 token ids
    max_new_tokens: int
    temperature: float = 0.0
    eos_token_id: Optional[int] = None
    key: Optional[jax.Array] = None      # PRNG key when temperature > 0
    tenant: str = "default"              # cost-attribution dimension
    priority: int = 0                    # scheduling class: HIGHER is
    #                                      more important (admission
    #                                      order, shed exemption,
    #                                      preemption protection)
    deadline_s: Optional[float] = None   # TTL from submit; the request
    #                                      expires in queue or is
    #                                      evicted from the running
    #                                      batch once it is spent
    #                                      (default off)
    prompt_spec: Optional[dict] = None   # failover journal only: a
    #                                      derivation spec (trace seed,
    #                                      rid, lengths) the admission
    #                                      journal records INSTEAD of
    #                                      inline prompt tokens, so a
    #                                      re-dispatch rebuilds the
    #                                      exact prompt as a pure
    #                                      function of the spec


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


@dataclasses.dataclass
class RequestOutput:
    rid: int
    tokens: np.ndarray                   # generated ids (<= max_new_tokens)
    prompt_len: int
    preemptions: int = 0                 # times this request was evicted
    tenant: str = "default"
    cost: Optional[RequestCost] = None   # monitor on: the attribution
    #                                      record; monitor off: None
    finish_reason: str = "completed"     # completed | expired | shed —
    #                                      every request that entered
    #                                      the engine ends in exactly
    #                                      one (rejected submissions
    #                                      never enter)
    retry_after_s: Optional[float] = None  # shed only: demand-model
    #                                      backoff hint
    shed_reason: Optional[str] = None    # shed only: the typed policy
    #                                      reason (displacement /
    #                                      drain) — what submit-time
    #                                      sheds carry on the
    #                                      EngineOverloaded they raise


class _Slot:
    __slots__ = ("req", "kv_len", "gen", "tokens", "pending", "done",
                 "keys", "preemptions", "t_first", "t_last",
                 "cost", "t_tick", "steps0", "ng", "ng_n")

    def __init__(self, req: Request, keys: np.ndarray):
        self.req = req
        self.kv_len = 0          # KV positions written (prompt + decoded)
        self.gen = 0             # tokens sampled so far
        self.tokens: List[int] = []
        self.pending = 0         # last sampled token (KV not yet written)
        self.done = False
        self.keys = keys         # [max_new, 2] uint32 sampling keys
        self.preemptions = 0
        self.t_first = None      # first-token wall stamp (monitor on)
        self.t_last = None       # latest-token wall stamp (monitor on)
        self.cost = None         # the request's RequestCost (monitor on)
        self.t_tick = None       # last page-seconds integration stamp
        self.steps0 = 0          # engine decode_steps at admission
        self.ng = None           # spec decode: bigram draft table over
        #                          this request's own context (lazy)
        self.ng_n = 0            # context tokens folded into ng so far


class EngineStats:
    def __init__(self):
        self.admitted = 0
        self.completed = 0
        self.preempted = 0
        self.expired = 0         # retired by their submit-time deadline
        self.shed = 0            # refused/ended by overload policy
        self.decode_steps = 0
        self.tokens_generated = 0    # incl. the token sampled at prefill
        self.tokens_decoded = 0      # emitted by decode steps only
        self.tokens_prefilled = 0
        self.tokens_discarded = 0    # thrown away by preemption recompute
        self.peak_pages_in_use = 0
        # rows of recurrent state (a family that keeps one; else all 0)
        self.state_rows_in_use = 0
        self.peak_state_rows_in_use = 0
        self.state_rows_assigned = 0     # rows handed out, re-admissions too
        self._occ_steps = 0      # decode steps weighted by slot count
        # shared-prefix radix cache (FLAGS_serving_prefix_cache)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0     # prompt tokens not re-prefilled
        self.prefix_evictions = 0        # radix nodes dropped by pressure
        # n-gram speculative decode (FLAGS_serving_spec_decode)
        self.spec_rounds = 0     # per-slot verify windows dispatched
        self.spec_drafted = 0    # draft tokens proposed (C-1 per round)
        self.spec_accepted = 0   # drafts accepted by greedy verify

    def occupancy(self) -> float:
        """Useful-token fraction of the decode grid: decode-emitted
        tokens / (decode steps x slots). Empty slots, done-masked chunk
        tails and drain phases all count against it — the honest
        number."""
        return (self.tokens_decoded / self._occ_steps
                if self._occ_steps else 0.0)

    def as_dict(self) -> dict:
        return {"admitted": self.admitted, "completed": self.completed,
                "preempted": self.preempted,
                "expired": self.expired, "shed": self.shed,
                "decode_steps": self.decode_steps,
                "tokens_generated": self.tokens_generated,
                "tokens_prefilled": self.tokens_prefilled,
                "tokens_discarded": self.tokens_discarded,
                "peak_pages_in_use": self.peak_pages_in_use,
                "batch_occupancy": round(self.occupancy(), 4),
                "prefix_lookups": self.prefix_lookups,
                "prefix_hits": self.prefix_hits,
                "prefix_tokens_saved": self.prefix_tokens_saved,
                "prefix_evictions": self.prefix_evictions,
                "spec_rounds": self.spec_rounds,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted}


@jax.named_scope("head")
def _sample_rows(logits, temps, keys, sampled=True):
    """Vectorised per-slot sampling: greedy rows where temperature is 0,
    else categorical on the tempered logits with that slot's own key —
    row-for-row the same draw the ring-buffer ``generate`` makes, so
    fixed-seed parity holds. ``sampled=False`` (every live slot greedy)
    skips the threefry/gumbel draw entirely — per-token RNG is real
    money at small model sizes."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not sampled:
        return greedy
    drawn = jax.vmap(lambda row, t, k: jax.random.categorical(
        k, row / jnp.maximum(t, 1e-6)))(logits, temps, keys)
    return jnp.where(temps > 0, drawn.astype(jnp.int32), greedy)


def _join_first(tokens, at, tok):
    """A prefill group's first tokens put at their slots among the decode
    chunk's pending tokens, on the device (``at`` names the slot grid's
    length for a dummy row, which is dropped)."""
    return tokens.at[at].set(tok, mode="drop")


def _decode_chunk(family, config, chunk, sampled, params, cache,
                  block_tables, state_rows, tokens, kv_len, done, gen, keys,
                  temps, max_new, eos):
    """``chunk`` decode steps as one program: write the pending token's
    KV, attend, sample the next. Done slots coast (writes dropped via
    length 0, outputs masked to -1; a recurrent state's row untouched).
    ``cache`` is the whole cache, one donated pytree; ``state_rows`` is
    the slots' row table (None where the family keeps no state)."""

    def body(carry, key_t):
        cache, tok, kvl, done, gen = carry
        n = jnp.where(done, 0, kvl + 1)
        cache, logits = cache_decode_step(
            family, params, cache, block_tables, n, tok, config, state_rows)
        kvl = jnp.where(done, kvl, kvl + 1)
        nxt = _sample_rows(logits, temps, key_t, sampled)
        emitted = jnp.where(done, -1, nxt)
        gen = gen + jnp.where(done, 0, 1)
        hit_eos = (~done) & (nxt == eos)
        done = done | hit_eos | (gen >= max_new)
        tok = jnp.where(emitted >= 0, nxt, tok)
        return (cache, tok, kvl, done, gen), emitted

    (cache, tok, kvl, done, gen), emitted = jax.lax.scan(
        body, (cache, tokens, kv_len, done, gen), keys, length=chunk)
    return cache, tok, kvl, done, gen, emitted


class ServingEngine:
    """Continuous-batching decode over a paged KV cache.

    ``family`` is a model module exposing the decoder seam
    (models.llama / models.moe; models.falcon_h1, which also keeps a
    recurrent state a sequence beside the pages); ``params`` may be the
    bf16 tree or the weight-only int8 tree from
    ``family.quantize_weights``."""

    def __init__(self, family, params, config, *, num_slots: int = 8,
                 max_len: Optional[int] = None,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 decode_chunk: int = 4, watermark: float = 0.0,
                 kv_dtype=None, kv_quant: Optional[bool] = None,
                 priority_admission: Optional[bool] = None,
                 tenant_inflight_cap: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 shed_on_burn: Optional[bool] = None,
                 slo_preemption: Optional[bool] = None,
                 failover: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 spec_decode: Optional[bool] = None):
        # Overload policies (ROADMAP item 5, acting half). Each kwarg
        # defaults to its FLAGS_serving_* flag (the make_train_step
        # guard=None pattern); every flag defaults OFF, and with all of
        # them off the scheduler is byte-identical to the pre-policy
        # engine — the existing parity tests are the contract.
        from ..core import flags as _eflags

        def _opt(v, flag):
            return _eflags.flag_value(flag) if v is None else v
        self._priority_admission = bool(
            _opt(priority_admission, "serving_priority_admission"))
        # negatives clamp to 0 = uncapped/unbounded (the "-1 means
        # unlimited" convention; a raw -1 cap would read `0 >= -1` for
        # every tenant and block admission forever)
        self._tenant_cap = max(0, int(
            _opt(tenant_inflight_cap, "serving_tenant_inflight_cap")))
        self._max_queue = max(0, int(
            _opt(max_queue, "serving_max_queue")))
        self._shed_on_burn = bool(
            _opt(shed_on_burn, "serving_shed_on_burn"))
        self._slo_preemption = bool(
            _opt(slo_preemption, "serving_slo_preemption"))
        # Exactly-once failover (inference/failover.py): the flag only
        # OFFERS durability — journaling starts when a controller (or
        # test) calls attach_journal, the publish_frames opt-in shape.
        # Flag off and unattached: one None check per terminal event.
        self._failover = bool(_opt(failover, "serving_failover"))
        # Per-token-latency optimizations (ROADMAP item 2): both
        # default off; flags-off scheduling and emitted tokens are
        # byte-identical (the parity tests pin it). The PrefixCache
        # itself is created after the page pool below.
        self._prefix_on = bool(_opt(prefix_cache, "serving_prefix_cache"))
        self._spec_decode = bool(_opt(spec_decode, "serving_spec_decode"))
        # Quantized memory plane (ROADMAP perf item): int8 page pools
        # with per-page per-kv-head scale planes. Off = full-precision
        # pools, byte-identical contents and tokens.
        self._kv_quant = bool(_opt(kv_quant, "serving_kv_quant"))
        self._journal = None
        self._draining = False
        self._deadlines_seen = False   # sticky: first deadline request
        #                                arms the per-step expiry scan
        self.family = family
        self.params = params
        self.config = config
        self.num_slots = int(num_slots)
        self.decode_chunk = int(decode_chunk)
        E.enforce(self.decode_chunk >= 1, "decode_chunk must be >= 1")
        max_len = int(max_len if max_len is not None
                      else config.max_position_embeddings)
        kv_dtype = kv_dtype if kv_dtype is not None else config.dtype
        if page_size is None:
            from ..kernels import autotune as _at
            page_size = _at.paged_page_size(
                num_slots, config.num_attention_heads,
                config.num_key_value_heads, config.head_dim,
                -(-max_len // 16) * 16, kv_dtype,
                kv_quant=self._kv_quant)
        self.page_size = int(page_size)
        self.max_len = -(-max_len // self.page_size) * self.page_size
        self.max_pages_per_seq = self.max_len // self.page_size
        if num_pages is None:
            num_pages = self.num_slots * self.max_pages_per_seq
        E.enforce(num_pages >= self.max_pages_per_seq,
                  f"pool of {num_pages} pages cannot hold even one "
                  f"max-length sequence ({self.max_pages_per_seq} pages)")
        self.watermark_pages = int(watermark * num_pages)
        # A family that declares a recurrent state gets a row of it a
        # slot, beside the pages. What shares or rewinds pages has no
        # counterpart for a state yet, and a guess is worse than a refusal.
        shapes = getattr(family, "state_shapes", None)
        self._recurrent = shapes is not None
        if self._recurrent:
            for on, what, missing in (
                    (self._prefix_on, "serving_prefix_cache",
                     "a snapshot of the state at the shared prefix's end"),
                    (self._spec_decode, "serving_spec_decode",
                     "a rollback of the state past the rejected drafts"),
                    (self._kv_quant, "serving_kv_quant",
                     "a quantized form of the state beside int8 pages")):
                E.enforce(not on, f"FLAGS_{what} with {family.__name__}: "
                          f"its sequences keep a recurrent state beside "
                          f"their pages, and {missing} is not written",
                          error=E.UnimplementedError)
        self.cache = PagedKVCache(
            config, num_pages, self.page_size, self.max_pages_per_seq,
            kv_dtype, kv_quant=self._kv_quant,
            state_shapes=shapes(config) if self._recurrent else None,
            state_rows=self.num_slots)
        # radix shared-prefix cache over the pool's committed pages;
        # None (flag off) short-circuits every hook to the original code
        self._prefix = PrefixCache(self.cache.alloc) if self._prefix_on \
            else None
        self.queue: deque = deque()
        self.slots: List[Optional[_Slot]] = [None] * self.num_slots
        self.outputs: Dict[int, RequestOutput] = {}
        self.stats = EngineStats()
        self._rng_fallback = 0

        self._prefill_fns: dict = {}     # (S_pad, sampled) -> jitted
        # shared-prefix tail prefills keyed by (g, S_tail, ctx_pages,
        # sampled); spec verify windows keyed by chunk length
        self._prefill_shared_fns: dict = {}
        self._spec_fns: dict = {}
        # chunk programs keyed by (length, sampled): greedy-only skips
        # per-token RNG; the 4x "turbo" length engages when every live
        # slot is guaranteed to run it end-to-end (no retire/join could
        # happen mid-chunk), quartering per-chunk host+dispatch overhead
        # through the long middle of large generations
        self.turbo_chunk = self.decode_chunk * 4
        def chunk_fn(c, s):
            # a def, not a functools.partial: the function's name is the
            # program's (XLA module jit_decode_chunk) in every trace
            def decode_chunk(*args):
                return _decode_chunk(family, config, c, s, *args)
            return jax.jit(decode_chunk, donate_argnums=(1,))

        self._chunk_fns = {
            (c, s): chunk_fn(c, s)
            for c in (self.decode_chunk, self.turbo_chunk)
            for s in (False, True)}
        # programs already called once: the first call of each compiles
        # (or loads), and runs under a serving.compile span
        self._called: set = set()
        # KV-page absmax sampling (monitor/numerics.py): 1-in-N decode
        # chunks dispatch a tiny per-layer per-page |K|/|V| max over
        # the pool AFTER the chunk's emitted-grid download has already
        # synchronized the device — zero added block_until_ready calls
        # at any rate (PR 9's pattern, pinned by test)
        self._kv_chunks = 0
        self._kv_absmax_fn = None
        # Fleet SLO federation (monitor/federation.py): an attached
        # FramePublisher rides the per-scheduler-step host tick — one
        # None check per step when unattached, pure host reads when
        # attached (zero added device synchronizations at any rate)
        self._frame_pub = None
        # registered-program FLOPs, cached per registry key: the cost
        # plane reads it once per chunk, not once per slot, and the
        # cached value keeps the per-dispatch cost at one dict lookup
        self._flops_by_key: dict = {}
        # device-side slot state, reused across chunks until a
        # join/retire/preempt (state) or page-table change (bt) dirties it
        self._dev: dict = {}
        self._state_dirty = True
        self._bt_dirty = True
        # prefills dispatched whose first tokens are still on the device
        # (_prefill_group: what reads them, the tokens, their slots);
        # empty whenever step() has returned
        self._unfetched = deque()
        self._join = jax.jit(_join_first)
        self._joins = set()      # group sizes whose join is compiled
        self._zero_rows = {}     # a greedy prefill group's temp and key, by g
        self._sampled = False
        self._zero_keys = {
            c: jnp.zeros((c, self.num_slots, 2), jnp.uint32)
            for c in (self.decode_chunk, self.turbo_chunk)}
        _monitor.set_gauge("serving.pages.total",
                           self.cache.num_pages,
                           doc="KV page pool capacity")
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
        self._engine_uid = _monitor.programs.next_uid()
        if _monitor.enabled() or _mserver.plane_active():
            _mserver.register_health_provider(
                f"serving:{self._engine_uid}",
                _engine_health_provider(weakref.ref(self)))
        # Sharding inspector (distributed/introspect.py): the param
        # tree's per-leaf layout for /sharding — pure serving runs
        # populate the view with no training loop in sight. Self-gated
        # on the monitor flag (off path computes + registers nothing).
        from ..distributed import introspect as _introspect
        _introspect.register_sharded_tree(
            f"serving:{self._engine_uid}.params", self.params)

    def _record_serving_program(self, spec_key, name, jitted, args,
                                kwargs, donated=()):
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
        reset-recovery seam (ensure_sharded_tree): a mid-run
        ``monitor.reset()`` repopulates ``/sharding`` on the next
        dispatch, like the program registry itself."""
        from ..distributed import introspect as _introspect
        from ..monitor import programs as _programs
        _introspect.ensure_sharded_tree(
            f"serving:{self._engine_uid}.params", lambda: self.params)
        key = ("engine", self._engine_uid) + spec_key
        if _programs.has_record(key):
            _programs.note_hit(key)
            return key
        _programs.record_jit_call(key, name, jitted, args,
                                  kwargs=kwargs, source="serving",
                                  donated=donated)
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

    # -- submission ---------------------------------------------------------

    def _reject_reason(self, req: Request):
        """``(why this submission must be refused, None)``, or
        ``(None, (prompt ndarray, max_new int, temperature float))``
        when it is well-formed — the validated+coerced values ride back
        and submit writes them ONTO the request, so a coercible-but-
        wrong-typed field (temperature="0.7", max_new_tokens=2.9) can
        never pass screening here and still detonate later in the
        scheduler. Every check runs on the HOST copy before the request
        touches any engine state — anything that would otherwise raise
        inside a compiled prefill/decode chunk (and kill the loop for
        every in-flight request) is turned into a rejection here
        instead."""
        def bad(reason):
            return reason, None
        try:
            prompt = np.asarray(req.prompt)
        except Exception:
            return bad("prompt is not array-like")
        if prompt.ndim != 1:
            return bad(f"prompt must be 1-D token ids, got shape "
                       f"{prompt.shape}")
        plen = int(prompt.shape[0])
        if plen < 1:
            return bad("empty prompt")
        if not np.issubdtype(prompt.dtype, np.integer):
            return bad(f"prompt dtype {prompt.dtype} is not an integer "
                       "token-id type")
        vocab = int(self.config.vocab_size)
        lo, hi = int(prompt.min()), int(prompt.max())
        if lo < 0 or hi >= vocab:
            return bad(f"prompt token ids outside [0, {vocab}): min {lo}, "
                       f"max {hi}")
        try:
            max_new = int(req.max_new_tokens)
            if max_new != req.max_new_tokens:   # 2.9 must not pass as 2
                return bad(f"max_new_tokens {req.max_new_tokens!r} is "
                           "not an integral count")
        except (TypeError, ValueError, OverflowError):
            # OverflowError: int(float('inf')) — must reject typed,
            # not crash the caller
            return bad(f"max_new_tokens {req.max_new_tokens!r} is not "
                       "an int")
        if max_new < 1:
            return bad(f"max_new_tokens must be >= 1, got {max_new}")
        if plen + max_new > self.max_len:
            return bad(f"prompt {plen} + max_new {max_new} exceeds "
                       f"max_len {self.max_len}")
        try:
            temp = float(req.temperature)
        except (TypeError, ValueError):
            return bad(f"temperature {req.temperature!r} is not a float")
        if not math.isfinite(temp) or temp < 0.0:
            return bad(f"temperature must be finite and >= 0, got {temp}")
        tenant = req.tenant
        if tenant is None:
            tenant = "default"
        else:
            try:
                tenant = str(tenant)
            except Exception:
                return bad("tenant is not string-coercible")
            tenant = tenant or "default"
            # content is NOT restricted — exposition escapes hostile
            # bytes and the slo plane bounds cardinality — but a label
            # value is not a document
            if len(tenant) > 128:
                return bad(f"tenant name of {len(tenant)} chars exceeds "
                           "the 128-char limit")
        try:
            priority = int(req.priority)
            if priority != req.priority:     # 1.5 must not pass as 1
                return bad(f"priority {req.priority!r} is not an "
                           "integral class")
        except (TypeError, ValueError, OverflowError):
            return bad(f"priority {req.priority!r} is not an int")
        deadline = req.deadline_s
        if deadline is not None:
            try:
                deadline = float(deadline)
            except (TypeError, ValueError, OverflowError):
                # OverflowError: float(10**400) — reject typed, don't
                # crash the caller (the max_new_tokens precedent)
                return bad(f"deadline_s {req.deadline_s!r} is not a "
                           "float")
            if not math.isfinite(deadline) or deadline <= 0.0:
                return bad("deadline_s must be finite and > 0, got "
                           f"{deadline}")
        return None, (prompt, max_new, temp, tenant, priority, deadline)

    def submit(self, req: Request):
        """Queue a request, or raise :class:`RequestRejected` (typed,
        counted) when it is malformed — the engine and every in-flight
        request are untouched either way until admission. With the
        overload policies on (all default-off), a well-formed
        submission may instead be SHED with :class:`EngineOverloaded`
        (typed, counted, ``retry_after_s`` hint): the queue is bounded
        (``max_queue``), an SLO fast-burn sheds priority<=0 work
        (``shed_on_burn``), and a draining replica refuses everything.
        A higher-priority submission arriving at a full queue displaces
        the lowest strictly-lower-priority queued request instead (the
        displaced one ends in ``outputs`` with
        ``finish_reason="shed"``)."""
        reason, norm = self._reject_reason(req)
        if reason is not None:
            _monitor.inc("serving.requests.rejected",
                         doc="malformed submissions refused at the "
                             "door (engine state untouched)")
            _trace.instant("serving.reject", rid=req.rid, reason=reason)
            if _monitor.enabled():
                # availability = non-rejected fraction: the refusal
                # must enter the SLO window, attributed to whatever
                # tenant the submission claimed (best-effort — the
                # rejection may be ABOUT the tenant field)
                try:
                    tenant = str(req.tenant or "default")[:128]
                except Exception:
                    tenant = "default"
                _slo.record_rejected(tenant or "default")
                _forensics.note_terminal(req.rid, "rejected",
                                         reason=reason,
                                         tenant=tenant or "default")
            raise RequestRejected(req.rid, reason)
        # the scheduler consumes the NORMALIZED values it was screened
        # on — the original coercible-but-wrong-typed fields must not
        # ride into the loop
        (req.prompt, req.max_new_tokens, req.temperature,
         req.tenant, req.priority, req.deadline_s) = norm
        if getattr(req, "_submitted", False):
            # re-admission of a previously-submitted object (the client
            # kept it): per-run mutable state must not carry over — the
            # cost record restarts, TTFT/e2e re-anchor, a stale
            # deadline anchor must not expire the new run, and the
            # preemption count is the new run's. (Preemption re-queues
            # re-enter via appendleft, not submit, and deliberately
            # keep all of it — the record follows the request across
            # ONE run.) The PRNG key is the exception: _keys_for pinned
            # the first run's key onto req.key, so a resubmission
            # replays byte-identical tokens.
            req._t0 = None
            req._t_enqueue = None
            req._cost = None
            req._t_deadline = None
            req._preempt_count = 0
        # overload gates, in severity order: a draining replica refuses
        # everything; an SLO fast-burn sheds best-effort work; a full
        # bounded queue sheds (or displaces for higher priority). All
        # three raise BEFORE the request touches any engine state.
        if self._draining:
            self._shed_submit(req, "engine is draining")
        if (self._shed_on_burn and req.priority <= 0
                and _monitor.enabled()
                and _slo.burn_alerting(load_only=True)):
            # load_only: the trigger reads the LATENCY burn — the
            # sheds this gate produces are availability-bad records,
            # and feeding them back would lock best-effort traffic
            # out long after the real overload cleared
            self._shed_submit(req, "SLO fast-burn alerting; "
                                   "priority<=0 work shed")
        if self._max_queue and len(self.queue) >= self._max_queue:
            victim = self._displaceable_pos(req.priority)
            if victim is None:
                self._shed_submit(
                    req, f"queue full ({self._max_queue}) and no "
                         f"lower-priority request to displace")
            else:
                shed = self.queue[victim]
                del self.queue[victim]
                _forensics.decision(
                    "displace", rid=shed.rid, reason="queue_full",
                    queue_depth=len(self.queue) + 1,
                    max_queue=self._max_queue, by_rid=req.rid,
                    by_priority=req.priority,
                    victim_priority=getattr(shed, "priority", 0))
                self._finish_shed(
                    shed, "displaced by higher-priority request "
                          f"{req.rid!r}")
        if req.deadline_s is not None:
            req._t_deadline = time.perf_counter() + req.deadline_s
            self._deadlines_seen = True
        plen = int(req.prompt.shape[0])
        if _monitor.enabled():
            now = time.perf_counter()
            # t0 anchors TTFT/e2e (first submission wins); t_enqueue is
            # refreshed by preemption re-queues and anchors queue_wait
            req._t0 = getattr(req, "_t0", None) or now
            req._t_enqueue = now
            # the cost record follows the REQUEST across preemption
            # re-queues (they re-enter via appendleft, not submit —
            # but a client resubmitting the same object keeps it too)
            if getattr(req, "_cost", None) is None:
                req._cost = RequestCost(tenant=req.tenant,
                                        priority=req.priority)
            _trace.instant("serving.enqueue", rid=req.rid, prompt=plen,
                           max_new=req.max_new_tokens,
                           tenant=req.tenant)
            _forensics.note(req.rid, "enqueue", t=now,
                            tenant=req.tenant, priority=req.priority,
                            prompt=plen, max_new=req.max_new_tokens)
        req._submitted = True
        if self._journal is not None:
            # journal AFTER every gate that could still refuse the
            # request (a shed/rejected submission never entered the
            # engine and must not be re-dispatched), and pin the
            # sampling key BEFORE the record is written so a
            # re-dispatch replays byte-identical tokens
            if req.temperature > 0.0 and req.key is None:
                self._rng_fallback += 1
                req.key = jax.random.PRNGKey(self._rng_fallback)
            self._journal.admit(req)
        self.queue.append(req)

    # -- overload policy: shedding, deadlines, drain ------------------------

    def autoscale_payload(self) -> dict:
        """The autoscale demand model (``monitor/slo.demand_model``)
        over THIS engine's state — works with the monitor off (shedding
        needs a ``retry_after_s`` hint regardless), and is the
        per-replica signal the elastic serving controller consumes.
        Slots count as live while RESIDENT (done-but-unretired
        included): a finished request's output only materializes at
        the next ``step``'s retire, so ``drain_safe`` here matches
        :attr:`drain_complete` — a controller acting on it can never
        stop a replica while an output is still trapped in a slot.
        (The ``serving.autoscale.*`` gauges tick inside ``step`` after
        retirement, where the two notions coincide.)"""
        resident = sum(1 for s in self.slots if s is not None)
        return _slo.demand_model(
            len(self.queue), resident, self.num_slots,
            self.cache.alloc.free_pages / self.cache.num_pages
            if self.cache.num_pages else 0.0)

    def _retry_after(self) -> float:
        return _slo.retry_after_hint(self.autoscale_payload())

    def publish_frames(self, name: str, dir_path: Optional[str] = None,
                       *, min_interval_s: float = 0.25, client=None,
                       local_only: bool = False, slo_fn=None):
        """Opt this replica into fleet SLO federation
        (``monitor/federation.py``): attach a frame publisher that
        emits a compact versioned telemetry frame — autoscale payload,
        per-objective burn/compliance, bounded tenant aggregates,
        request terminal-state counters, drain state — on the existing
        per-scheduler-step host tick, through the name-keyed heartbeat
        transport (``dir_path`` file beats + coordination-service KV;
        the frame IS the liveness beat). Pure host reads; zero added
        device synchronizations at any publish rate. Returns the
        publisher (one per engine; re-attaching replaces it)."""
        from ..monitor import federation as _fed
        self._frame_pub = _fed.FramePublisher(
            name, dir_path=dir_path, client=client,
            local_only=local_only,
            min_interval_s=min_interval_s, slo_fn=slo_fn)
        self._frame_pub.maybe_publish(self, force=True)
        return self._frame_pub

    def attach_journal(self, name: str, dir_path: Optional[str] = None,
                       *, client=None):
        """Opt this replica into the exactly-once admission journal
        (``inference/failover.py``; requires ``failover=True`` /
        ``FLAGS_serving_failover`` — the flag gates the durability
        layer, this call names the replica and the transport). Every
        subsequent admission is journaled write-through and every
        terminal event writes a completion marker, so the elastic
        controller can re-dispatch work stranded by a crash without
        ever double-serving a finished request. Returns the journal
        (one per engine; re-attaching replaces it)."""
        if not self._failover:
            return None
        from .failover import AdmissionJournal
        self._journal = AdmissionJournal(name, dir_path=dir_path,
                                         client=client)
        return self._journal

    def _shed_submit(self, req: Request, why: str):
        """Refuse a WELL-FORMED submission by overload policy: typed
        :class:`EngineOverloaded` with the demand-model backoff hint,
        before the request touches any engine state."""
        hint = self._retry_after()
        self.stats.shed += 1
        _monitor.inc("serving.requests.shed",
                     doc="admissible work refused by overload policy "
                         "(bounded queue, SLO burn, displacement, "
                         "drain) with a retry_after_s hint")
        tenant = getattr(req, "tenant", "default") or "default"
        _trace.instant("serving.shed", rid=req.rid, reason=why,
                       retry_after_s=hint, tenant=tenant)
        if _monitor.enabled():
            _slo.record_shed(tenant)
            _forensics.decision("shed", rid=req.rid, reason=why,
                                queue_depth=len(self.queue),
                                priority=getattr(req, "priority", 0),
                                draining=self._draining)
            _forensics.note_terminal(req.rid, "shed", reason=why,
                                     tenant=tenant,
                                     retry_after_s=round(hint, 3))
        raise EngineOverloaded(req.rid, why, hint)

    def _displaceable_pos(self, priority: int) -> Optional[int]:
        """Queue position of the displacement victim for an arriving
        ``priority`` request at a full queue: the LOWEST-priority
        queued request, oldest first, and only when strictly below the
        newcomer — equal-priority work is never displaced (FIFO
        fairness within a class). Preemption re-queues are EXEMPT:
        they are admitted work mid-recompute, and admitted work is
        never dropped (the begin_drain contract) — a newcomer, however
        important, outranks only work that has not been served yet."""
        pos, lowest = None, None
        for j, r in enumerate(self.queue):
            if getattr(r, "_preempt_count", 0) > 0:
                continue
            p = getattr(r, "priority", 0)
            if p < priority and (lowest is None or p < lowest):
                pos, lowest = j, p
        return pos

    def _finish_shed(self, req: Request, why: str):
        """End a QUEUED request as shed (displacement or drain): it
        leaves through ``outputs`` with ``finish_reason="shed"`` and
        the backoff hint — never silently dropped (its submitter
        already returned from ``submit``)."""
        hint = self._retry_after()
        self.stats.shed += 1
        _monitor.inc("serving.requests.shed")
        mon = _monitor.enabled()
        cost = getattr(req, "_cost", None) if mon else None
        if cost is not None:
            t_enq = getattr(req, "_t_enqueue", None)
            if t_enq is not None:
                cost.queue_wait_ms += (time.perf_counter() - t_enq) * 1e3
        if mon:
            if cost is not None:
                # the shed rides availability like a rejection, but
                # its consumption (prefill before a preemption,
                # page-seconds, the queue wait above) folds into the
                # tenant aggregates — the tenant PAID for it
                _slo.record_request(dict(cost.as_dict(),
                                         rejected=True, shed=True))
            else:
                _slo.record_shed(getattr(req, "tenant", "default")
                                 or "default")
        self.outputs[req.rid] = RequestOutput(
            rid=req.rid, tokens=np.zeros(0, np.int32),
            prompt_len=int(np.asarray(req.prompt).shape[0]),
            preemptions=getattr(req, "_preempt_count", 0),
            tenant=getattr(req, "tenant", "default"),
            cost=cost, finish_reason="shed", retry_after_s=hint,
            shed_reason=why)
        if self._journal is not None:
            self._journal.finish(req.rid, "shed")
        tenant = getattr(req, "tenant", "default") or "default"
        _trace.instant("serving.shed", rid=req.rid, reason=why,
                       retry_after_s=hint, tenant=tenant)
        if mon:
            _forensics.decision("shed", rid=req.rid, reason=why,
                                queued=True,
                                priority=getattr(req, "priority", 0),
                                draining=self._draining)
            _forensics.note_terminal(req.rid, "shed", reason=why,
                                     tenant=tenant,
                                     retry_after_s=round(hint, 3))

    def begin_drain(self, shed_queued: bool = True):
        """Enter the drain lifecycle: stop admitting new work (submit
        sheds with ``EngineOverloaded``), shed the not-yet-admitted
        queue (``shed_queued=False`` lets it finish instead), and let
        live decodes run to retirement — ``drain_complete`` flips once
        nothing is queued or resident. A preemption during drain still
        re-queues for recompute (finishing live work may require it);
        only NEW submissions are refused. Idempotent."""
        from ..testing import faults as _faults
        _faults.hit("serving.drain")
        already = self._draining
        self._draining = True
        _trace.instant("serving.drain.begin", queued=len(self.queue),
                       again=already)
        if shed_queued:
            keep: deque = deque()
            while self.queue:
                r = self.queue.popleft()
                if getattr(r, "_preempt_count", 0) > 0:
                    # a preemption re-queue is ADMITTED live work
                    # awaiting recompute — the drain contract finishes
                    # it. This also makes repeat begin_drain calls
                    # (the elastic controller retries every tick)
                    # safe: after the first call, only preemption
                    # re-queues can enter the queue.
                    keep.append(r)
                else:
                    self._finish_shed(r, "engine is draining")
            self.queue = keep
        if self._frame_pub is not None:
            # drain state must reach the federation controller now,
            # not a rate-limit later — but only the TRANSITION forces:
            # the controller re-invokes begin_drain every retry tick
            # of a slow drain, and forcing each call would bypass the
            # rate limit into per-tick transport I/O
            self._frame_pub.maybe_publish(self, force=not already)

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drain_complete(self) -> bool:
        """No queued and no resident requests (done-but-unretired slots
        count as resident: their outputs only materialize at the next
        ``step``)."""
        return not self.queue and all(s is None for s in self.slots)

    def _expire_due(self):
        """Deadline/TTL enforcement: retire every request whose
        submit-time deadline is spent — queued requests leave with no
        tokens, running ones are evicted with the tokens they had
        (pages freed, counted in the cost record). Runs once per
        ``step`` and only after some request has carried a deadline
        (``_deadlines_seen`` — deadline-free serving never pays the
        scan). A DONE slot past its deadline retires normally: its
        output is complete."""
        now = time.perf_counter()
        if self.queue and any(
                getattr(r, "_t_deadline", None) is not None
                and now >= r._t_deadline for r in self.queue):
            keep = deque()
            for r in self.queue:
                t = getattr(r, "_t_deadline", None)
                if t is not None and now >= t:
                    self._finish_expired(r, slot_idx=None, now=now)
                else:
                    keep.append(r)
            self.queue = keep
        for idx in range(self.num_slots):
            slot = self.slots[idx]
            if slot is None or slot.done:
                continue
            t = getattr(slot.req, "_t_deadline", None)
            if t is not None and now >= t:
                self._finish_expired(slot.req, slot_idx=idx, now=now)

    def _finish_expired(self, req: Request, slot_idx: Optional[int],
                        now: float):
        """End ``req`` as deadline-expired: from the queue (no tokens)
        or evicted from a running slot (partial tokens delivered —
        they were sampled and are the client's to keep, so the
        generated-discarded==emitted token contract holds)."""
        mon = _monitor.enabled()
        cost = getattr(req, "_cost", None) if mon else None
        tokens = np.zeros(0, np.int32)
        preemptions = getattr(req, "_preempt_count", 0)
        if slot_idx is not None:
            slot = self.slots[slot_idx]
            self.slots[slot_idx] = None
            self._state_dirty = self._bt_dirty = True
            if cost is not None and slot.t_tick is not None:
                # final page-seconds tick, read before the free
                cost.page_seconds += (
                    self.cache.alloc.page_count(req.rid)
                    * (now - slot.t_tick))
            self.cache.alloc.free(req.rid)
            tokens = np.asarray(slot.tokens, np.int32)
            preemptions = slot.preemptions
            if cost is not None:
                cost.grid_steps += (self.stats.decode_steps
                                    - slot.steps0) * self.num_slots
        elif cost is not None:
            t_enq = getattr(req, "_t_enqueue", None)
            if t_enq is not None:
                cost.queue_wait_ms += (now - t_enq) * 1e3
        self.stats.expired += 1
        _monitor.inc("serving.requests.expired",
                     doc="requests retired by their submit-time "
                         "deadline (expired in queue or evicted from "
                         "the running batch)")
        if cost is not None:
            cost.preemptions = preemptions
            t0 = getattr(req, "_t0", None)
            if t0 is not None:
                cost.e2e_ms = (now - t0) * 1e3
            if cost.grid_steps > 0:
                cost.slot_share = round(
                    cost.slot_steps / cost.grid_steps, 6)
            # the SLO window counts an expiry BAD for availability and
            # excludes it from the latency objectives (monitor/slo.py)
            _slo.record_request(dict(cost.as_dict(), expired=True))
        self.outputs[req.rid] = RequestOutput(
            rid=req.rid, tokens=tokens,
            prompt_len=int(np.asarray(req.prompt).shape[0]),
            preemptions=preemptions,
            tenant=getattr(req, "tenant", "default"),
            cost=cost, finish_reason="expired")
        if self._journal is not None:
            self._journal.finish(req.rid, "expired",
                                 tokens=int(tokens.shape[0]))
        tenant = getattr(req, "tenant", "default") or "default"
        _trace.instant("serving.expire", rid=req.rid,
                       tokens=int(tokens.shape[0]),
                       in_slot=slot_idx is not None, tenant=tenant)
        if mon:
            if slot_idx is not None:
                _forensics.decision("evict", rid=req.rid,
                                    reason="deadline", slot=slot_idx,
                                    tokens=int(tokens.shape[0]))
            _forensics.note_terminal(
                req.rid, "expired", t=now,
                e2e_ms=(cost.e2e_ms if cost is not None
                        and cost.e2e_ms else None),
                tenant=tenant, tokens=int(tokens.shape[0]),
                in_slot=slot_idx is not None)

    # -- scheduling ---------------------------------------------------------

    def _bucket(self, plen: int) -> int:
        """Padded prompt length: next power-of-two page count (bounds the
        number of distinct prefill compiles at log2(max_pages))."""
        pages = self.cache.alloc.pages_for(plen)
        b = 1
        while b < pages:
            b *= 2
        return min(b, self.max_pages_per_seq) * self.page_size

    def _prefill_fn(self, g: int, s_pad: int, sampled: bool):
        fn = self._prefill_fns.get((g, s_pad, sampled))
        if fn is None:
            family, config = self.family, self.config

            def _pf(params, ids, cache, page_rows, slen, temp, key,
                    state_rows=None):
                cache, logits = cache_prefill(family, params, ids, config,
                                              cache, page_rows, slen,
                                              state_rows)
                # the first tokens sample INSIDE the prefill program —
                # one dispatch per admission GROUP, not two per request
                tok = _sample_rows(logits, temp, key, sampled)
                return cache, tok

            fn = jax.jit(_pf, donate_argnums=(2,))
            self._prefill_fns[(g, s_pad, sampled)] = fn
        return fn

    def _prefill_shared_fn(self, g: int, s_eff: int, ncp: int,
                           sampled: bool):
        """Tail-only prefill over ``ncp`` cached prefix pages: same
        sample-inside-the-program contract as ``_prefill_fn``, one
        compile per (group, tail, ctx-pages, sampled) specialization
        (ctx length is page-bucketed like the tail, so the key space
        stays log-bounded)."""
        fn = self._prefill_shared_fns.get((g, s_eff, ncp, sampled))
        if fn is None:
            family, config = self.family, self.config

            def _pf(params, ids, cache, page_rows, slen, temp, key,
                    ctx_rows):
                cache, logits = cache_prefill_shared(
                    family, params, ids, config, cache, page_rows, slen,
                    ctx_rows)
                tok = _sample_rows(logits, temp, key, sampled)
                return cache, tok

            fn = jax.jit(_pf, donate_argnums=(2,))
            self._prefill_shared_fns[(g, s_eff, ncp, sampled)] = fn
        return fn

    def _spec_fn(self, C: int):
        """Greedy verify window for speculative decode: one program
        per chunk length, argmax inside (the host only ever needs the
        predicted ids)."""
        fn = self._spec_fns.get(C)
        if fn is None:
            family, config = self.family, self.config

            def spec_verify(params, cache, bt, drafts, kv_len, live):
                cache, logits = cache_verify_window(
                    family, params, drafts, config, cache, bt, kv_len,
                    live)
                return cache, jnp.argmax(
                    logits, axis=-1).astype(jnp.int32)

            fn = jax.jit(spec_verify, donate_argnums=(1,))
            self._spec_fns[C] = fn
        return fn

    def _first_call(self, fn):
        """A ``serving.compile`` span for the first call of a jitted
        serving program (it compiles, or loads from the cache), a null
        context after: a compile in the middle of serving is a named gap
        in a trace, not a slow step. The call itself stays inline at its
        site: a Python frame between the scheduler and a jitted call is
        not free while the program is traced (PERF.md section 6, PR 24)."""
        if id(fn) in self._called:
            return _NO_SPAN
        self._called.add(id(fn))
        return _trace.span("serving.compile")

    def _free_slack(self) -> int:
        """Free pages the admission watermark may count: the free list
        plus prefix-cache pages reclaimable on demand (one
        ``_evict_pages`` away from free) — cold cache entries must
        never jam admission. Flag off: exactly ``free_pages``."""
        free = self.cache.alloc.free_pages
        if self._prefix is not None:
            free += self._prefix.reclaimable()
        return free

    def _evict_pages(self, n: int) -> int:
        """LRU-evict prefix-cache entries until ``n`` pages hit the
        free list (or nothing evictable remains); returns pages freed.
        Flag off: a no-op 0."""
        if self._prefix is None:
            return 0
        before = self._prefix.evicted_nodes
        freed = self._prefix.evict(n)
        dropped = self._prefix.evicted_nodes - before
        if dropped:
            self.stats.prefix_evictions += dropped
            _monitor.inc("serving.prefix_cache.evictions", dropped,
                         doc="radix nodes dropped under pool pressure")
        return freed

    def _match_len(self, req: Request) -> int:
        """Cached page-aligned prefix length for a prompt (group-fill
        compatibility probe; refreshes matched nodes' LRU stamps)."""
        return self._prefix.match(np.asarray(req.prompt))[0]

    def _alloc_for(self, req: Request, s_pad: int):
        """Admission allocation through the radix prefix cache: fork
        the longest cached page-aligned prefix by refcount and take
        only the tail fresh, evicting LRU cache leaves under pool
        pressure. The match is re-run after every eviction round —
        eviction may drop the very nodes just matched, and a stale
        pages list must never be forked. Stamps ``req._pfx_cached``
        with the shared token count on success. Flag off: the original
        ``alloc`` call, byte-identical."""
        alloc = self.cache.alloc
        if self._prefix is None:
            return alloc.alloc(req.rid, s_pad)
        self.stats.prefix_lookups += 1
        _monitor.inc("serving.prefix_cache.lookups",
                     doc="admission prompt-prefix radix probes")
        need = alloc.pages_for(s_pad)
        while True:
            cached, pages = self._prefix.match(np.asarray(req.prompt))
            missing = (need - len(pages)) - alloc.free_pages
            if missing > 0:
                if self._evict_pages(missing) == 0:
                    return None
                continue
            got = alloc.alloc_prefix(req.rid, pages, s_pad) if cached \
                else alloc.alloc(req.rid, s_pad)
            if got is None:
                return None
            req._pfx_cached = cached
            if cached:
                self.stats.prefix_hits += 1
                self.stats.prefix_tokens_saved += cached
                _monitor.inc("serving.prefix_cache.hits",
                             doc="admissions that forked cached "
                                 "prefix pages")
                _monitor.inc("serving.prefix_cache.tokens_saved", cached,
                             doc="prompt tokens served from cached KV "
                                 "instead of prefill")
            return got

    def _keys_for(self, req: Request) -> np.ndarray:
        if req.temperature <= 0.0:
            return np.zeros((req.max_new_tokens, 2), np.uint32)
        key = req.key
        if key is None:
            self._rng_fallback += 1
            key = jax.random.PRNGKey(self._rng_fallback)
            # pin the fallback onto the request: a resubmission of the
            # same object (and a failover re-dispatch reading it from
            # the journal) replays byte-identical tokens instead of
            # drawing a fresh counter key
            req.key = key
        return np.asarray(jax.random.split(key, req.max_new_tokens),
                          np.uint32)

    def _compact(self):
        """Slot compaction: pack live slots into the low indices (block
        tables and device slot state are rebuilt on the next chunk, so
        this is a pure host permutation: a recurrent state stays in its
        row, which the rebuilt row table finds)."""
        live = [s for s in self.slots if s is not None]
        packed = live + [None] * (self.num_slots - len(live))
        if packed != self.slots:
            self.slots = packed
            self._state_dirty = self._bt_dirty = True

    def _retire(self, idx: int):
        slot = self.slots[idx]
        self.slots[idx] = None
        self._state_dirty = self._bt_dirty = True
        mon = _monitor.enabled()
        cost = slot.cost if mon else None
        if cost is not None and slot.t_tick is not None:
            # final page-seconds tick: pages held from the last chunk
            # edge until this retirement, read BEFORE the free below
            now_t = time.perf_counter()
            cost.page_seconds += (
                self.cache.alloc.page_count(slot.req.rid)
                * (now_t - slot.t_tick))
            slot.t_tick = now_t
        if self._prefix is not None and slot.kv_len >= self.page_size:
            # retirement insertion: only COMMITTED positions enter the
            # radix — the prompt plus the generated tokens whose KV is
            # already written (kv_len worth; the final pending token's
            # KV never was). insert() takes a cache hold on each newly
            # shared page BEFORE the free below, so the pages survive
            # the sequence's release with ref >= 1.
            prompt = np.asarray(slot.req.prompt, np.int32)
            plen = int(prompt.shape[0])
            gen_committed = slot.kv_len - plen
            stream = prompt if gen_committed <= 0 else np.concatenate(
                [prompt, np.asarray(slot.tokens[:gen_committed],
                                    np.int32)])
            self._prefix.insert(stream,
                                self.cache.alloc.seq_pages(slot.req.rid))
        self.cache.alloc.free(slot.req.rid)
        self.outputs[slot.req.rid] = RequestOutput(
            rid=slot.req.rid,
            tokens=np.asarray(slot.tokens, np.int32),
            prompt_len=int(np.asarray(slot.req.prompt).shape[0]),
            preemptions=slot.preemptions,
            tenant=getattr(slot.req, "tenant", "default"),
            cost=cost)
        if self._journal is not None:
            # the completion marker lands BEFORE the output can be
            # harvested: a crash after this point re-dispatches
            # nothing for this rid (exactly-once dedup)
            self._journal.finish(slot.req.rid, "completed",
                                 tokens=int(len(slot.tokens)))
        self.stats.completed += 1
        _monitor.inc("serving.requests.completed")
        if mon:
            now = time.perf_counter()
            t0 = getattr(slot.req, "_t0", None)
            if t0 is not None:
                e2e = (now - t0) * 1e3
                _observe_latency(
                    "serving.latency.e2e_ms", e2e,
                    "request lifetime: original enqueue to retirement")
                if cost is not None:
                    cost.e2e_ms = e2e
                if slot.t_first is not None:
                    # observed at retirement, not at prefill: a
                    # preempted request re-prefills, and only the
                    # surviving run's first token — the one the client
                    # keeps — counts. One sample per completed request.
                    ttft = (slot.t_first - t0) * 1e3
                    _observe_latency(
                        "serving.latency.ttft_ms", ttft,
                        "original enqueue to the prefill-sampled "
                        "first token the client keeps")
                    if cost is not None:
                        cost.ttft_ms = ttft
            if slot.gen > 1 and slot.t_first is not None \
                    and slot.t_last is not None:
                # mean inter-token time over the decode phase; t_last
                # is the arrival of the final emitted token (chunk-edge
                # resolution), t_first the prefill-sampled token
                tpot = (slot.t_last - slot.t_first) / (slot.gen - 1) * 1e3
                _observe_latency(
                    "serving.latency.tpot_ms", tpot,
                    "mean time per output token after the first")
                if cost is not None:
                    cost.tpot_ms = tpot
            if cost is not None:
                cost.preemptions = slot.preemptions
                # slot-occupancy share: fraction of the decode grid's
                # capacity this request held over its residencies
                # (cumulative across preemption re-runs; None when it
                # retired without a decode chunk in between)
                cost.grid_steps += (self.stats.decode_steps
                                    - slot.steps0) * self.num_slots
                cost.slot_share = round(
                    cost.slot_steps / cost.grid_steps, 6) \
                    if cost.grid_steps > 0 else None
                _slo.record_request(cost.as_dict())
            _trace.instant("serving.retire", rid=slot.req.rid,
                           tokens=slot.gen,
                           preemptions=slot.preemptions,
                           tenant=getattr(slot.req, "tenant", "default"))
            _forensics.note_terminal(
                slot.req.rid, "completed", t=now,
                e2e_ms=(cost.e2e_ms if cost is not None
                        and cost.e2e_ms else None),
                ttft_ms=(cost.ttft_ms if cost is not None
                         and cost.ttft_ms else None),
                tenant=getattr(slot.req, "tenant", "default"),
                tokens=slot.gen, preemptions=slot.preemptions)

    def _preempt_victim_idx(self) -> Optional[int]:
        """Pick the eviction victim. Default: the YOUNGEST live request
        (highest slot index — the original recompute policy). With
        ``slo_preemption`` on: the request with the LOWEST eviction
        cost, ordered by (priority, prior preemptions, accumulated
        work) — evict the least important class first; within a class
        protect repeat victims (anti-starvation) and then evict the
        request that is cheapest to recompute. Work comes from the
        per-request cost record (prefill+decode tokens, cumulative
        across re-runs) when the monitor keeps one, else the current
        run's KV length — the monitor-off proxy of the same quantity."""
        if not self._slo_preemption:
            for idx in range(self.num_slots - 1, -1, -1):
                slot = self.slots[idx]
                if slot is not None and not slot.done:
                    return idx
            return None
        best_idx, best_key = None, None
        for idx in range(self.num_slots):
            slot = self.slots[idx]
            if slot is None or slot.done:
                continue
            work = slot.kv_len
            if slot.cost is not None:
                work = slot.cost.prefill_tokens + slot.cost.decode_tokens
            key = (getattr(slot.req, "priority", 0), slot.preemptions,
                   work, -idx)       # final tie-break: youngest
            if best_key is None or key < best_key:
                best_idx, best_key = idx, key
        return best_idx

    def _preempt_one(self) -> bool:
        """Evict one live request (recompute policy: pages freed,
        request requeued at the FRONT so it re-runs before newcomers);
        the victim is :meth:`_preempt_victim_idx`'s. False when
        nothing can be evicted."""
        self._first_tokens()
        idx = self._preempt_victim_idx()
        if idx is None:
            return False
        slot = self.slots[idx]
        self.slots[idx] = None
        self._state_dirty = self._bt_dirty = True
        now = time.perf_counter() if _monitor.enabled() else None
        cost = slot.cost if now is not None else None
        if cost is not None and slot.t_tick is not None:
            # final page-seconds tick for this run, read before
            # the free — an evicted request PAID for the pages
            # it held even though the work is recomputed
            cost.page_seconds += (
                self.cache.alloc.page_count(slot.req.rid)
                * (now - slot.t_tick))
        self.cache.alloc.free(slot.req.rid)
        slot.req._preempt_count = getattr(
            slot.req, "_preempt_count", 0) + 1
        self.queue.appendleft(slot.req)
        self.stats.preempted += 1
        # the evicted request's sampled-but-unretired tokens are
        # recomputed from scratch: move them to the discarded
        # column so generated - discarded stays == emitted
        self.stats.tokens_discarded += slot.gen
        _monitor.inc("serving.requests.preempted")
        _monitor.inc("serving.tokens.discarded", slot.gen,
                     doc="sampled tokens thrown away by "
                         "preemption recompute")
        if now is not None:
            # the re-queue refreshes t_enqueue: the NEXT wait
            # accumulates onto the record's cumulative
            # queue_wait_ms at re-admission (the histogram
            # observes each wait once, the record keeps the sum)
            slot.req._t_enqueue = now
            if cost is not None:
                cost.discarded_tokens += slot.gen
                cost.grid_steps += (self.stats.decode_steps
                                    - slot.steps0) \
                    * self.num_slots
            tenant = getattr(slot.req, "tenant", "default") \
                or "default"
            _trace.instant("serving.preempt", rid=slot.req.rid,
                           discarded=slot.gen, tenant=tenant)
            # the victim-selection inputs that chose this slot — the
            # _preempt_victim_idx key, recorded so the eviction is
            # auditable (forensics decision ring + the victim's own
            # timeline)
            work = slot.kv_len
            if cost is not None:
                work = (cost.prefill_tokens + cost.decode_tokens)
            policy = "slo" if self._slo_preemption else "youngest"
            victim = dict(policy=policy, slot=idx,
                          priority=getattr(slot.req, "priority", 0),
                          prior_preemptions=slot.preemptions,
                          work=int(work))
            _forensics.decision("preempt", rid=slot.req.rid,
                                discarded=slot.gen, **victim)
            _forensics.note(slot.req.rid, "preempt", t=now,
                            tenant=tenant, discarded=slot.gen,
                            **victim)
        return True

    def _defer(self, req: "Request", reason: str, **inputs):
        """Record one admission-scan deferral (forensics timeline +
        decision ring, both self-gated and coalescing — a head request
        blocked on the same reason for many steps is ONE record with a
        count, not a flood)."""
        _forensics.note_defer(req.rid, reason, **inputs)
        _forensics.decision("defer", rid=req.rid, reason=reason,
                            **inputs)

    def _admit(self):
        # PAIRED SCANS: this FIFO body and _admit_policy below share
        # the admission-control math (watermark, idle override,
        # alloc-failure enforce, group fill) by deliberate copy — the
        # flag-off path must stay byte-identical to the pre-policy
        # engine, so it is never routed through policy code. A fix to
        # the shared math MUST be applied to both.
        if self._priority_admission or self._tenant_cap:
            return self._admit_policy()
        while self.queue:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                self._defer(self.queue[0], "no_free_slot",
                            queue_depth=len(self.queue))
                break
            req = self.queue[0]
            plen = int(np.asarray(req.prompt).shape[0])
            s_pad = max(self._bucket(plen), self.page_size)
            need = s_pad // self.page_size
            idle = not any(s is not None and not s.done
                           for s in self.slots)
            if (self._free_slack() - need < self.watermark_pages
                    and not idle):        # head-of-line admission control
                self._defer(req, "watermark",
                            free_slack=self._free_slack(), need=need,
                            watermark_pages=self.watermark_pages,
                            queue_depth=len(self.queue))
                break
            self.queue.popleft()
            if self._alloc_for(req, s_pad) is None:
                self.queue.appendleft(req)
                self._defer(req, "alloc_failed", need=need,
                            free_pages=self.cache.alloc.free_pages,
                            queue_depth=len(self.queue))
                # an idle engine that cannot place its head request will
                # never make progress — that is a sizing error, not a
                # transient
                E.enforce(not idle,
                          f"request {req.rid} needs {need} pages but only "
                          f"{self.cache.alloc.free_pages} exist free on an "
                          f"idle engine", error=E.ResourceExhaustedError)
                break
            # group same-bucket waiters into this prefill dispatch (a
            # bounded look-through keeps overall FIFO fairness while
            # letting one program admit several requests). With the
            # prefix cache on, co-grouped requests must also match the
            # head's cached prefix length — the tail program's context
            # page count is a static compile-time constant per group.
            head_cached = getattr(req, "_pfx_cached", 0)
            group = [req]
            scanned = 0
            while (len(group) < len(free)
                   and scanned < len(self.queue)
                   and self._free_slack() - need
                   >= self.watermark_pages):
                cand = self.queue[scanned]
                cp = int(np.asarray(cand.prompt).shape[0])
                if max(self._bucket(cp), self.page_size) != s_pad or (
                        self._prefix is not None
                        and self._match_len(cand) != head_cached):
                    scanned += 1
                    continue
                if self._alloc_for(cand, s_pad) is None:
                    break
                if getattr(cand, "_pfx_cached", 0) != head_cached:
                    # an eviction inside _alloc_for shifted the match;
                    # not groupable this pass — leave it queued
                    self.cache.alloc.free(cand.rid)
                    scanned += 1
                    continue
                del self.queue[scanned]
                group.append(cand)
            self._prefill_group(free, group, s_pad)

    def _admit_policy(self):
        """Priority-class admission (``priority_admission`` /
        ``tenant_inflight_cap``): each pass admits the
        highest-priority eligible request — ties broken by queue
        position, i.e. arrival order, with preemption re-queues at the
        front — instead of the FIFO head, and a tenant already holding
        ``tenant_inflight_cap`` live slots is skipped (its requests
        wait without blocking other tenants' head-of-line). The cap
        WITHOUT priority admission keeps strict FIFO order among
        eligible requests — the cap alone must not change scheduling
        class semantics (the flag doc's contract). Same page
        watermark, idle override, and same-bucket grouping as the FIFO
        scan; grouping may co-admit lower-priority same-bucket waiters
        into slots of the dispatch that would otherwise idle — a
        bounded, one-dispatch-deep inversion traded for batched
        prefill. PAIRED with _admit's FIFO body (see the comment
        there): fixes to the shared admission-control math go in
        both."""
        cap = self._tenant_cap
        inflight: Dict[str, int] = {}
        if cap:
            for s in self.slots:
                if s is not None:
                    t = getattr(s.req, "tenant", "default")
                    inflight[t] = inflight.get(t, 0) + 1
        while self.queue:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                self._defer(self.queue[0], "no_free_slot",
                            queue_depth=len(self.queue))
                break
            pos = None
            for j, r in enumerate(self.queue):
                if cap and inflight.get(
                        getattr(r, "tenant", "default"), 0) >= cap:
                    continue
                if not self._priority_admission:
                    pos = j               # cap-only: first eligible (FIFO)
                    break
                if pos is None or getattr(r, "priority", 0) \
                        > getattr(self.queue[pos], "priority", 0):
                    pos = j
            if pos is None:
                # every waiter's tenant is at cap
                self._defer(self.queue[0], "tenant_cap", cap=cap,
                            queue_depth=len(self.queue))
                break
            req = self.queue[pos]
            plen = int(np.asarray(req.prompt).shape[0])
            s_pad = max(self._bucket(plen), self.page_size)
            need = s_pad // self.page_size
            idle = not any(s is not None and not s.done
                           for s in self.slots)
            if (self._free_slack() - need < self.watermark_pages
                    and not idle):
                self._defer(req, "watermark",
                            free_slack=self._free_slack(), need=need,
                            watermark_pages=self.watermark_pages,
                            queue_depth=len(self.queue))
                break
            del self.queue[pos]
            if self._alloc_for(req, s_pad) is None:
                self.queue.insert(pos, req)
                self._defer(req, "alloc_failed", need=need,
                            free_pages=self.cache.alloc.free_pages,
                            queue_depth=len(self.queue))
                E.enforce(not idle,
                          f"request {req.rid} needs {need} pages but only "
                          f"{self.cache.alloc.free_pages} exist free on an "
                          f"idle engine", error=E.ResourceExhaustedError)
                break
            head_cached = getattr(req, "_pfx_cached", 0)
            group = [req]
            if cap:
                t = getattr(req, "tenant", "default")
                inflight[t] = inflight.get(t, 0) + 1
            # group fill in PRIORITY order (ties: queue position), not
            # queue order — an equal-or-higher-priority same-bucket
            # waiter must not lose its seat in the dispatch to an
            # earlier-queued lower-priority one. Cap-only mode fills
            # in queue order (FIFO semantics preserved).
            if self._priority_admission:
                order = sorted(
                    range(len(self.queue)),
                    key=lambda j: (
                        -getattr(self.queue[j], "priority", 0), j))
            else:
                order = list(range(len(self.queue)))
            picked: List[int] = []
            for j in order:
                if len(group) >= len(free):
                    break
                if (self._free_slack() - need
                        < self.watermark_pages):
                    break
                cand = self.queue[j]
                cp = int(np.asarray(cand.prompt).shape[0])
                ct = getattr(cand, "tenant", "default")
                if max(self._bucket(cp), self.page_size) != s_pad or (
                        cap and inflight.get(ct, 0) >= cap) or (
                        self._prefix is not None
                        and self._match_len(cand) != head_cached):
                    continue
                if self._alloc_for(cand, s_pad) is None:
                    break
                if getattr(cand, "_pfx_cached", 0) != head_cached:
                    # eviction inside _alloc_for shifted the match;
                    # not groupable this pass — leave it queued
                    self.cache.alloc.free(cand.rid)
                    continue
                picked.append(j)
                group.append(cand)
                if cap:
                    inflight[ct] = inflight.get(ct, 0) + 1
            for j in sorted(picked, reverse=True):
                del self.queue[j]
            self._prefill_group(free, group, s_pad)

    def _prefill_group(self, free: List[int], group: List["Request"],
                       s_pad: int):
        """One batched prefill for same-bucket requests, padded to a
        power-of-two group size (bounds compiles at log2(slots) per
        bucket); dummy rows carry all-sentinel page tables and never
        touch the pool."""
        need = s_pad // self.page_size
        mon = _monitor.enabled()
        t_admit = None
        if mon:
            t_admit = time.perf_counter()
            _forensics.decision(
                "admit", rid=group[0].rid, group=len(group),
                bucket=s_pad, free_slots=len(free),
                queue_depth=len(self.queue),
                pfx_cached=int(getattr(group[0], "_pfx_cached", 0)))
            for r in group:
                wait_ms = None
                t_enq = getattr(r, "_t_enqueue", None)
                if t_enq is not None:
                    wait_ms = (t_admit - t_enq) * 1e3
                    _observe_latency(
                        "serving.latency.queue_wait_ms", wait_ms,
                        "enqueue (or preemption re-queue) to admission")
                    cost = getattr(r, "_cost", None)
                    if cost is not None:
                        # CUMULATIVE across preemption re-queues: the
                        # histogram above observes each wait once; the
                        # record answers "how long did this request
                        # spend queued in total"
                        cost.queue_wait_ms += wait_ms
                _trace.instant("serving.admit", rid=r.rid)
                # the admit event carries the prefix-cache match result
                # (cached prefix length this group was grouped on)
                _forensics.note(
                    r.rid, "admit", t=t_admit, bucket=s_pad,
                    group=len(group),
                    wait_ms=round(wait_ms, 3)
                    if wait_ms is not None else None,
                    pfx_cached=int(getattr(r, "_pfx_cached", 0)))
        with _trace.span("serving.prefill", group=len(group),
                         s_pad=s_pad):
            with _trace.span("serving.prefill.build"):
                g = 1
                while g < len(group):
                    g *= 2
                # with the prefix cache on, every member of this group shares
                # the same cached page-aligned prefix length (admission grouped
                # by it): the program prefills only the uncached tail, reading
                # the shared context pages without ever writing them
                cached = int(getattr(group[0], "_pfx_cached", 0)) \
                    if self._prefix is not None else 0
                ncp = cached // self.page_size
                s_eff = s_pad - cached
                need_eff = need - ncp
                ids = np.zeros((g, s_eff), np.int32)
                rows = np.full((g, need_eff), self.cache.num_pages, np.int32)
                ctx_rows = np.full((g, ncp), self.cache.num_pages, np.int32)
                slen = np.ones(g, np.int32)
                temps = np.zeros(g, np.float32)
                keys = np.zeros((g, 2), np.uint32)
                slots = []
                for j, r in enumerate(group):
                    plen = int(np.asarray(r.prompt).shape[0])
                    ids[j, :plen - cached] = np.asarray(r.prompt,
                                                        np.int32)[cached:]
                    brow = self.cache.alloc.block_row(r.rid, need)
                    ctx_rows[j] = brow[:ncp]
                    rows[j] = brow[ncp:]
                    slen[j] = plen - cached
                    temps[j] = r.temperature
                    slot = _Slot(r, self._keys_for(r))
                    slot.kv_len = plen
                    slot.preemptions = getattr(r, "_preempt_count", 0)
                    keys[j] = slot.keys[0]
                    slots.append(slot)
                sampled = any(r.temperature > 0 for r in group)
                pf = self._prefill_shared_fn(g, s_eff, ncp, sampled) \
                    if cached else self._prefill_fn(g, s_pad, sampled)
                up = dict(ids=ids, page_rows=rows, slen=slen)
                # Where no request of the group can end on its first token
                # (none names an EOS), nothing the scheduler decides before
                # the next chunk's dispatch reads that token: it goes to its
                # slot on the device (_join_first), the chunk's inputs are
                # built while the device prefills, the chunk starts where the
                # prefill ends, and the host reads the token after that.
                later = all(r.eos_token_id is None for r in group)
                if later:
                    up["at"] = np.full(g, self.num_slots, np.int32)
                    up["at"][:len(group)] = free[:len(group)]
                    if g not in self._joins:
                        # compiled here, beside the group's prefill program,
                        # not at a first join in the middle of serving
                        self._joins.add(g)
                        with _trace.span("serving.compile"):
                            self._join(*jax.device_put((
                                np.zeros(self.num_slots, np.int32),
                                up["at"], np.zeros(g, np.int32))))
                if sampled:
                    up.update(temp=temps, key=keys)
                if cached:
                    up["ctx_rows"] = ctx_rows
                if self._recurrent:
                    with _trace.span("serving.step.state"):
                        # each request's own row; a dummy names nobody's
                        up["state_rows"] = self.cache.state_row_table(
                            [r.rid for r in group]
                            + [None] * (g - len(group)))
                pf_kwargs = jax.device_put(up)       # one call for them all
                if not sampled:     # greedy: neither is read, nor sent again
                    if g not in self._zero_rows:
                        self._zero_rows[g] = jax.device_put(
                            dict(temp=temps, key=keys))
                    pf_kwargs.update(self._zero_rows[g])
                at = pf_kwargs.pop("at", None)
                pf_args = (self.params, pf_kwargs.pop("ids"), self.cache.pool)
            exec_rec = None
            pf_flops_share = None
            if mon:
                # introspection-registry record, BEFORE the dispatch that
                # donates the pool buffers (once per specialization)
                key = self._record_serving_program(
                    ("serving.prefill_shared", g, s_eff, ncp, sampled)
                    if cached else ("serving.prefill", g, s_pad, sampled),
                    f"serving.prefill_shared[g{g},s{s_eff},ctx{ncp}]"
                    if cached else f"serving.prefill[g{g},s{s_pad}]",
                    pf, pf_args, pf_kwargs, donated=(2,))
                from ..monitor import exectime as _exectime
                exec_rec = _exectime.maybe_sample(key, feed_last=False)
                # modeled-FLOPs attribution: the registered program's
                # cost-analysis count split across the real requests that
                # shared this dispatch (dummy pad rows attribute nowhere)
                pf_flops = self._program_flops(key)
                if pf_flops:
                    pf_flops_share = pf_flops / len(group)
            with _trace.span("serving.prefill.dispatch"), \
                    self._first_call(pf):
                self.cache.pool, tok_a = pf(*pf_args, **pf_kwargs)
            # the slots are taken now, with all that no token decides
            for j, (r, slot) in enumerate(zip(group, slots)):
                self.cache.alloc.advance(r.rid, int(slen[j]) + cached)
                slot.gen = 1
                slot.done = slot.gen >= r.max_new_tokens
                self.slots[free[j]] = slot
                self.stats.admitted += 1
                self.stats.tokens_generated += 1
                self.stats.tokens_prefilled += int(slen[j])
                _monitor.inc("serving.requests.admitted")
                # the prefill-sampled first token counts here so the
                # counter agrees with stats.tokens_generated
                _monitor.inc("serving.tokens.generated")
                _monitor.inc("serving.tokens.prefilled", int(slen[j]))
            self._state_dirty = self._bt_dirty = True

            def first_tokens():
                with _trace.span("serving.prefill.fetch"):
                    # the np.asarray download syncs the device — the span
                    # ends (and TTFT is stamped) when the first token actually
                    # EXISTS on the host, not when the dispatch returned
                    toks = np.asarray(tok_a)
                if exec_rec is not None:
                    # the download above already synchronized: rec(None) adds
                    # ZERO extra block_until_ready calls at this seam
                    exec_rec(None)
                t_first = None
                if mon:
                    # TTFT is NOT observed here: a preemption would discard
                    # this run's tokens and re-prefill, double-sampling the
                    # histogram with a first token the client never saw. The
                    # slot carries t_first to _retire, which observes once per
                    # completed request. The lifecycle instant still marks
                    # every prefill (preempted runs included) in the trace.
                    t_first = time.perf_counter()
                    for r in group:
                        _trace.instant("serving.first_token", rid=r.rid)
                        # pure host bookkeeping AFTER the np.asarray download
                        # above already synchronized: zero added device syncs
                        _forensics.note(r.rid, "first_token", t=t_first)
                with _trace.span("serving.prefill.emit"):
                    for j, (r, slot) in enumerate(zip(group, slots)):
                        tok = int(toks[j])
                        slot.tokens.append(tok)
                        slot.pending = tok
                        slot.t_first = slot.t_last = t_first
                        if mon:
                            slot.cost = getattr(r, "_cost", None)
                            # page-seconds integrate from admission (pages were
                            # allocated in _admit) at chunk-edge resolution
                            slot.t_tick = t_admit
                            slot.steps0 = self.stats.decode_steps
                            if slot.cost is not None:
                                slot.cost.prefill_tokens += int(slen[j])
                                if cached:
                                    slot.cost.prefix_cached_tokens += cached
                                    if pf_flops_share:
                                        # modeled: the tail program's per-
                                        # padded-token cost scaled by the
                                        # tokens the cache served — what a
                                        # full prefill would have added, to
                                        # first order
                                        slot.cost.prefill_flops_saved += (
                                            pf_flops_share / s_eff * cached)
                                if pf_flops_share:
                                    slot.cost.model_flops += pf_flops_share
                        slot.done = slot.done or tok == r.eos_token_id

            self._unfetched.append((first_tokens, tok_a, at))
            if not later:
                self._first_tokens()

    def _first_tokens(self):
        """Wait for the first tokens of every prefill dispatched and not
        yet read, and give them to their slots."""
        while self._unfetched:
            self._unfetched.popleft()[0]()

    def _pick_chunk(self, live_idx: List[int]) -> int:
        """Turbo chunk when no retire/join/EOS could land mid-chunk:
        the slot grid is full, everyone's remaining run covers it, and
        nobody can stop early on EOS. Occupancy is then provably
        unaffected, and per-chunk overhead amortises 4x further."""
        if len(live_idx) < self.num_slots:
            return self.decode_chunk
        for i in live_idx:
            s = self.slots[i]
            if (s.req.eos_token_id is not None
                    or s.req.max_new_tokens - s.gen < self.turbo_chunk):
                return self.decode_chunk
        return self.turbo_chunk

    def _ensure_chunk_capacity(self, live_idx: List[int],
                               chunk: int) -> List[int]:
        """Reserve pages for up to ``chunk`` appends per live slot,
        preempting the youngest requests on OOM. Returns the (possibly
        shrunk) live index list."""
        i = 0
        while i < len(live_idx):
            idx = live_idx[i]
            slot = self.slots[idx]
            if slot is None:              # preempted by an earlier pass
                live_idx.pop(i)
                continue
            appends = min(chunk,
                          slot.req.max_new_tokens - slot.gen + 1)
            got = self.cache.alloc.ensure(slot.req.rid,
                                          slot.kv_len + appends)
            if got is None:
                # reclaim cold prefix-cache pages before sacrificing a
                # live request (flag off: a no-op 0, byte-identical)
                if self._evict_pages(1) == 0:
                    E.enforce(self._preempt_one(),
                              "page pool exhausted with nothing left to "
                              "preempt", error=E.ResourceExhaustedError)
                continue                  # retry this slot
            if got[0] or got[1]:
                self._bt_dirty = True
            self.cache.apply_cow(got[1])
            i += 1
        return [idx for idx in live_idx if self.slots[idx] is not None]

    def step(self) -> bool:
        """One scheduling iteration: expire (when any request carries a
        deadline) -> retire -> compact -> admit -> one decode chunk.
        Returns False when the engine is fully idle.

        The span tree of a step (``monitor.trace.span``: in the ring
        when the monitor is on, and in any open ``jax.profiler`` session
        on the device trace's clock), one span a boundary between
        phases, or between host work and waiting for the device::

            serving.step
              serving.step.expire | .retire | .compact
              serving.step.admit          policy, page allocation, grouping
                serving.prefill           per admitted group
                  .build                  numpy rows, keys, their upload
                  .dispatch               the jitted call
                    serving.compile       first call of a program only
                  .fetch                  the download that waits
                  .emit                   first tokens to their slots
              serving.step.reserve        chunk length, pages, preemption
              serving.decode_chunk | serving.spec_chunk
                .build | .dispatch [serving.compile] | .fetch | .emit

        A prefill's ``.fetch`` and ``.emit`` come after the chunk's
        ``.dispatch`` where they were put off (``_prefill_group``).
        """
        with _trace.span("serving.step"):
            if self._deadlines_seen:
                with _trace.span("serving.step.expire"):
                    self._expire_due()
            with _trace.span("serving.step.retire"):
                for idx in range(self.num_slots):
                    if self.slots[idx] is not None \
                            and self.slots[idx].done:
                        self._retire(idx)
            with _trace.span("serving.step.compact"):
                self._compact()
            with _trace.span("serving.step.admit"):
                self._admit()
            _monitor.set_gauge("serving.queue.depth", len(self.queue),
                               doc="requests waiting for admission")
            in_use = self.cache.alloc.used_pages
            self.stats.peak_pages_in_use = max(
                self.stats.peak_pages_in_use, in_use)
            _monitor.set_gauge("serving.pages.in_use", in_use,
                               doc="KV pages currently allocated")
            if self._recurrent:
                st, alloc = self.stats, self.cache.alloc
                st.state_rows_in_use = alloc.used_rows
                st.peak_state_rows_in_use = max(st.peak_state_rows_in_use,
                                                alloc.used_rows)
                st.state_rows_assigned = alloc.rows_assigned

            live_idx = [i for i, s in enumerate(self.slots)
                        if s is not None and not s.done]
            if _monitor.enabled():
                # autoscale feed (monitor/slo.py): one host tick per
                # scheduling step — queue depth, live slots, page slack.
                # The gauges themselves are recomputed at scrape time.
                _slo.note_sched_tick(
                    len(self.queue), len(live_idx), self.num_slots,
                    self.cache.alloc.free_pages / self.cache.num_pages
                    if self.cache.num_pages else 0.0)
            if self._frame_pub is not None:
                # federation frame on the same host tick (rate-limited
                # inside; pure host state — zero device syncs)
                self._frame_pub.maybe_publish(self)
            if not live_idx:
                self._first_tokens()
                return bool(self.queue) or any(
                    s is not None for s in self.slots)
            with _trace.span("serving.step.reserve"):
                C = self._pick_chunk(live_idx)
                live_idx = self._ensure_chunk_capacity(live_idx, C)
            if not live_idx:
                self._first_tokens()
                return True
            if (self._spec_decode and C == self.turbo_chunk
                    and not any(self.slots[i].req.temperature > 0
                                for i in live_idx)):
                # greedy turbo chunk: verify a self-drafted window in
                # ONE model pass instead of C sequential decode steps.
                # The turbo preconditions (full grid, no EOS, remaining
                # run covers the chunk) already hold, so accept/reject
                # lands at the same chunk boundary the sequential path
                # downloads at.
                with _trace.step_span("serving.spec_chunk",
                                      self.stats.decode_steps, chunk=C,
                                      live=len(live_idx)):
                    return self._spec_step(live_idx, C)
            with _trace.step_span("serving.decode_chunk",
                                  self.stats.decode_steps, chunk=C,
                                  live=len(live_idx)):
                return self._chunk_step(live_idx, C)

    def _block_tables(self, live_idx: List[int]) -> np.ndarray:
        """The slot grid's block table; a slot that is not live reads no
        page."""
        live = set(live_idx)
        return self.cache.block_tables(
            [self.slots[i].req.rid if i in live else None
             for i in range(self.num_slots)])

    def _chunk_step(self, live_idx: List[int], C: int) -> bool:
        """``C`` sequential decode steps over the slot grid as one
        program, and the one download that brings its tokens back."""
        with _trace.span("serving.decode_chunk.build"):
            B = self.num_slots
            up = {}             # what this chunk uploads, in one call
            if self._bt_dirty:
                up["bt"] = self._block_tables(live_idx)
                self._bt_dirty = False
            if self._state_dirty:
                # (re)build the device-side slot state. The steady state —
                # chunk after chunk with no join/retire/new-page — reuses the
                # PREVIOUS chunk's returned device arrays untouched: the
                # scheduler's host work then stays off the per-token path.
                live = [self.slots[i] for i in live_idx]
                at = np.asarray(live_idx, np.intp)

                def col(values, dtype, fill=0):
                    a = np.full(B, fill, dtype)
                    a[at] = values
                    return a

                up.update(
                    tokens=col([s.pending for s in live], np.int32),
                    kv_len=col([s.kv_len for s in live], np.int32),
                    done=col(False, bool, True),
                    gen=col([s.gen for s in live], np.int32),
                    temps=col([s.req.temperature for s in live], np.float32),
                    max_new=col([s.req.max_new_tokens for s in live],
                                np.int32),
                    eos=col([-1 if s.req.eos_token_id is None
                             else s.req.eos_token_id for s in live],
                            np.int32, -1))
                self._sampled = any(s.req.temperature > 0 for s in live)
                if self._recurrent:
                    # where each slot's sequence keeps its state: after a
                    # compaction or a join this table moves, no state does
                    with _trace.span("serving.step.state"):
                        up["rows"] = self.cache.state_row_table(
                            [s.req.rid if s is not None and not s.done
                             else None for s in self.slots])
                self._state_dirty = False
            if up:
                self._dev.update(jax.device_put(up))
            for _, first, where in self._unfetched:
                # a slot admitted in this step: its pending token is still
                # on the device, and goes to its place there
                self._dev["tokens"] = self._join(self._dev["tokens"], where,
                                                 first)
            if self._sampled:
                keys = np.zeros((C, B, 2), np.uint32)
                for i in live_idx:
                    s = self.slots[i]
                    for t in range(C):
                        keys[t, i] = s.keys[min(s.gen + t, len(s.keys) - 1)]
                keys = jnp.asarray(keys)
            else:
                keys = self._zero_keys[C]  # greedy: keys are never read

        d = self._dev
        ck = self._chunk_fns[(C, self._sampled)]
        ck_args = (self.params, self.cache.pool, d["bt"], d.get("rows"),
                   d["tokens"], d["kv_len"], d["done"], d["gen"], keys,
                   d["temps"], d["max_new"], d["eos"])
        exec_rec = None
        ck_flops_share = None
        if _monitor.enabled():
            key = self._record_serving_program(
                ("serving.decode_chunk", C, self._sampled),
                f"serving.decode_chunk[c{C}"
                f"{',sampled' if self._sampled else ''}]",
                ck, ck_args, None, donated=(1,))
            from ..monitor import exectime as _exectime
            exec_rec = _exectime.maybe_sample(key, feed_last=False)
            # modeled-FLOPs attribution: the chunk program's registered
            # cost-analysis count split across the live slots sharing
            # this dispatch (done/empty slots ride along for free in
            # the static grid; the work exists because of the live
            # ones). None/0 when the backend never reported — skipped,
            # not fabricated.
            ck_flops = self._program_flops(key)
            if ck_flops:
                ck_flops_share = ck_flops / len(live_idx)
        with _trace.span("serving.decode_chunk.dispatch"), \
                self._first_call(ck):
            self.cache.pool, tok, kvl, done_a, gen_a, emitted = ck(*ck_args)
        self._dev.update(tokens=tok, kv_len=kvl, done=done_a, gen=gen_a)
        self._first_tokens()     # the prefills are done before the chunk is
        with _trace.span("serving.decode_chunk.fetch"):
            # ONE device->host transfer per chunk: every host-side fact
            # is derivable from the emitted grid (-1 = slot was done at
            # that step; a write and a sample happen exactly on non -1
            # steps). The download syncs, so the span's end — and the
            # t_chunk stamp below — is when the tokens reached the host.
            emitted = np.asarray(emitted)                # [C, B]
        if exec_rec is not None:
            # the emitted-grid download already synchronized this
            # chunk: rec(None) adds zero block_until_ready calls
            exec_rec(None)
        if _monitor.enabled():
            self._maybe_sample_kv_absmax()
        t_chunk = time.perf_counter() if _monitor.enabled() else None
        with _trace.span("serving.decode_chunk.emit"):
            new_tokens = 0
            cols = emitted.T.tolist()        # a slot's steps, a row each
            whole = bool((emitted >= 0).all())
            for i in live_idx:
                s = self.slots[i]
                toks = cols[i] if whole else [t for t in cols[i] if t >= 0]
                if toks:
                    s.tokens.extend(toks)
                    new_tokens += len(toks)
                    self.cache.alloc.advance(s.req.rid, len(toks))
                    s.kv_len += len(toks)
                    s.gen += len(toks)
                    s.pending = toks[-1]
                    s.t_last = t_chunk if t_chunk is not None else s.t_last
                if t_chunk is not None and s.cost is not None:
                    # cost attribution at the chunk edge the emitted-grid
                    # download above already synchronized: pure host reads
                    # (allocator page counts, the cached program FLOPs) —
                    # zero added device synchronizations at any rate
                    if s.t_tick is not None:
                        s.cost.page_seconds += (
                            self.cache.alloc.page_count(s.req.rid)
                            * (t_chunk - s.t_tick))
                    s.t_tick = t_chunk
                    s.cost.slot_steps += C
                    s.cost.decode_tokens += len(toks)
                    if ck_flops_share:
                        s.cost.model_flops += ck_flops_share
                s.done = s.gen >= s.req.max_new_tokens or (
                    s.req.eos_token_id is not None and bool(toks)
                    and toks[-1] == s.req.eos_token_id)
        self.stats.decode_steps += C
        self.stats.tokens_generated += new_tokens
        self.stats.tokens_decoded += new_tokens
        self.stats._occ_steps += C * self.num_slots
        occ = self.stats.occupancy()
        _monitor.set_gauge("serving.batch.occupancy", round(occ, 4),
                           doc="generated tokens / (decode steps x slots)")
        _monitor.inc("serving.tokens.generated", new_tokens)
        return True

    def _draft_for(self, s: "_Slot", C: int) -> np.ndarray:
        """Draft a C-token verify window for one sequence: position 0
        is the real pending token (its KV is the one unwritten commit),
        positions 1..C-1 come from a bigram table folded incrementally
        over the request's own context (prompt + emitted tokens), with
        repeat-last as the cold-miss fallback. Pure host work — the
        table is a dict on the slot, extended only over tokens appended
        since the last draft."""
        if s.ng is None:
            s.ng = {}
        prompt = np.asarray(s.req.prompt)
        plen = int(prompt.shape[0])
        total = plen + len(s.tokens)

        def at(p):
            return int(prompt[p]) if p < plen else int(s.tokens[p - plen])

        for p in range(max(s.ng_n, 2), total):
            s.ng[(at(p - 2), at(p - 1))] = at(p)
        s.ng_n = total
        out = np.empty(C, np.int32)
        out[0] = s.pending
        p2, p1 = at(total - 2), at(total - 1)
        for t in range(1, C):
            nxt = s.ng.get((p2, p1), p1)
            out[t] = nxt
            p2, p1 = p1, nxt
        return out

    def _spec_step(self, live_idx: List[int], C: int) -> bool:
        """One speculative verify round over the greedy turbo chunk:
        write all C drafted positions' KV, run ONE attention pass over
        the window, and accept the longest run where the model's greedy
        prediction confirms the next draft. Token-identity with the
        sequential path is by construction: draft position 0 is the
        real pending token, so prediction 0 is exactly the sequential
        path's next token; each further draft is only kept when it
        EQUALS the greedy prediction before it, and the first emitted
        token after any rejection is again the model's own prediction.
        (Identity is at the math level: the verify window is a
        differently-shaped program than the turbo chunk, so in reduced
        precision an argmax near-tie can flip — exact in f32.)
        Rejected positions' KV stays in the pool as garbage masked out
        by sequence length and overwritten by later commits."""
        self._first_tokens()           # a draft starts at the pending token
        with _trace.span("serving.spec_chunk.build"):
            B = self.num_slots
            if self._bt_dirty:
                self._dev["bt"] = jnp.asarray(self._block_tables(live_idx))
                self._bt_dirty = False
            drafts = np.zeros((B, C), np.int32)
            kv_len = np.zeros(B, np.int32)
            live_m = np.zeros(B, bool)
            for i in live_idx:
                s = self.slots[i]
                drafts[i] = self._draft_for(s, C)
                kv_len[i] = s.kv_len
                live_m[i] = True
            vf = self._spec_fn(C)
            vf_args = (self.params, self.cache.pool, self._dev["bt"],
                       jnp.asarray(drafts), jnp.asarray(kv_len),
                       jnp.asarray(live_m))
        exec_rec = None
        vf_flops_share = None
        if _monitor.enabled():
            key = self._record_serving_program(
                ("serving.spec_chunk", C),
                f"serving.spec_chunk[c{C}]", vf, vf_args, None,
                donated=(1,))
            from ..monitor import exectime as _exectime
            exec_rec = _exectime.maybe_sample(key, feed_last=False)
            vf_flops = self._program_flops(key)
            if vf_flops:
                vf_flops_share = vf_flops / len(live_idx)
        with _trace.span("serving.spec_chunk.dispatch"), \
                self._first_call(vf):
            self.cache.pool, preds_a = vf(*vf_args)
        with _trace.span("serving.spec_chunk.fetch"):
            preds = np.asarray(preds_a)                  # [B, C]
        if exec_rec is not None:
            exec_rec(None)
        if _monitor.enabled():
            self._maybe_sample_kv_absmax()
        t_chunk = time.perf_counter() if _monitor.enabled() else None
        with _trace.span("serving.spec_chunk.emit"):
            new_tokens = 0
            accepted_total = 0
            for i in live_idx:
                s = self.slots[i]
                dr = drafts[i]
                col = preds[i]
                a = 0
                while a < C - 1 and dr[a + 1] == col[a]:
                    a += 1
                emitted = [int(t) for t in col[:a + 1]]
                s.tokens.extend(emitted)
                new_tokens += len(emitted)
                accepted_total += a
                self.cache.alloc.advance(s.req.rid, len(emitted))
                s.kv_len += len(emitted)
                s.gen += len(emitted)
                s.pending = emitted[-1]
                s.t_last = t_chunk if t_chunk is not None else s.t_last
                if t_chunk is not None and s.cost is not None:
                    if s.t_tick is not None:
                        s.cost.page_seconds += (
                            self.cache.alloc.page_count(s.req.rid)
                            * (t_chunk - s.t_tick))
                    s.t_tick = t_chunk
                    s.cost.slot_steps += C
                    s.cost.decode_tokens += len(emitted)
                    if vf_flops_share:
                        s.cost.model_flops += vf_flops_share
                if t_chunk is not None:
                    # aggregate fold, no event append: spec rounds are
                    # per-chunk-rate and would flood the bounded timeline
                    _forensics.note_spec(s.req.rid, C - 1, a)
                # turbo preconditions rule out EOS; only the length bound
                # can finish a sequence here
                s.done = s.gen >= s.req.max_new_tokens
        self.stats.decode_steps += C
        self.stats.tokens_generated += new_tokens
        self.stats.tokens_decoded += new_tokens
        self.stats._occ_steps += C * self.num_slots
        self.stats.spec_rounds += len(live_idx)
        self.stats.spec_drafted += (C - 1) * len(live_idx)
        self.stats.spec_accepted += accepted_total
        occ = self.stats.occupancy()
        _monitor.set_gauge("serving.batch.occupancy", round(occ, 4),
                           doc="generated tokens / (decode steps x slots)")
        _monitor.inc("serving.tokens.generated", new_tokens)
        _monitor.inc("serving.spec.rounds", len(live_idx),
                     doc="per-sequence speculative verify rounds")
        _monitor.inc("serving.spec.drafted", (C - 1) * len(live_idx),
                     doc="n-gram draft tokens proposed for verification")
        _monitor.inc("serving.spec.accepted", accepted_total,
                     doc="draft tokens confirmed by the greedy verify")
        # the device-side sequential slot state is stale after a spec
        # round (tokens/kv_len/gen advanced on the host): rebuild it
        # before the next sequential chunk
        self._state_dirty = True
        return True

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
        in_use = np.flatnonzero(self.cache.alloc._ref > 0)
        if in_use.size == 0:
            return
        if self._kv_absmax_fn is None:
            if self._kv_quant:
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
        out = self._kv_absmax_fn(self.cache.pool["k"],
                                 self.cache.pool["v"])
        km = np.asarray(out[0])[:, in_use]
        vm = np.asarray(out[1])[:, in_use]
        _numerics.record_kv_absmax(km, vm)
        if self._kv_quant:
            scales = np.asarray(out[2])[:, in_use]
            clip = float(np.mean(np.asarray(out[3])[in_use]))
            _numerics.record_kv_quant(scales, clip)

    def run(self, requests=None, max_steps: int = 1_000_000
            ) -> Dict[int, RequestOutput]:
        """Drive the scheduler until every submitted request completes;
        returns {rid: RequestOutput}."""
        if requests:
            for r in requests:
                self.submit(r)
        steps = 0
        while self.step():
            steps += 1
            E.enforce(steps < max_steps,
                      f"engine did not drain within {max_steps} steps")
        return self.outputs
