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

What happened is reported, once an event, to ``inference/accounting.py``
(``self._acct``), which alone knows the planes that consume it (counters,
latency histograms, the per-request cost record on
``RequestOutput.cost``, the SLO window, forensics, the failover journal,
federation frames): docs/serving.md has the events. This module imports
nothing of them; ``engine.stats`` holds the same counts unconditionally.

Spans (always on; ``monitor.trace.span``): every phase of ``step()`` —
expire, retire, compact, admit with each group's prefill, the page
reservation, the decode or verify chunk — and inside prefill and chunk
the host's work apart from its waiting (``.build``, ``.dispatch``,
``.fetch``, ``.emit``; a program's first call under
``serving.compile``) is a span under the prefix ``serving.`` (the tree
is in ``ServingEngine.step``'s docstring). As ``jax.profiler``
annotations they are in ANY open profiler session on the device trace's
clock, so a device idle gap reads as what the host was doing. With no
session a span is a no-op of about a microsecond, ten to fifteen a step.
Add one only at a boundary between layers or between host work and
waiting, never inside a loop over slots. The device programs are named
to match: ``jit_decode_chunk``, ``jit_spec_verify``, ``jit__pf``,
``jit__join_first``. The jitted calls stay inline in ``_prefill_group``,
``_chunk_step`` and ``_spec_step``, accounting calls go beside them, never
round them, and those frames keep their size: a program's first call costs
0.2-0.6 s more or less by the BYTES of Python frame above it (a hot call
of the tracer that straddles the end of one of CPython's 16 KiB
frame-stack chunks maps and unmaps one each time; PERF.md section 6, PRs
24 and 29; frames and bytes are held by tests/test_engine_layering.py).

Overload control (docs/overload.md; every flag default OFF, flags-off
scheduling byte-identical to the policy-free engine): priority
admission with a per-tenant in-flight cap
(``FLAGS_serving_priority_admission``,
``FLAGS_serving_tenant_inflight_cap``); a bounded queue that sheds, or
displaces for a higher priority, with a typed :class:`EngineOverloaded`
carrying a ``retry_after_s`` hint (``FLAGS_serving_max_queue``; an SLO
fast-burn sheds priority<=0 work under ``FLAGS_serving_shed_on_burn``);
per-request deadlines (``Request.deadline_s``: expired in the queue, or
evicted from the batch with the tokens it had); SLO-aware preemption
(``FLAGS_serving_slo_preemption``: the lowest (priority, prior
preemptions, accumulated work) goes first, not the youngest); and the
drain lifecycle (:meth:`ServingEngine.begin_drain`: stop admitting, shed
the queue, finish live decodes; ``drain_complete`` gates the elastic
controller's scale-in).

Every submitted request ends in exactly one of completed / rejected /
expired / shed, with a typed reason — nothing is dropped silently.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import enforce as E
from ..monitor import trace as _trace
from .accounting import EngineAccounting, RequestCost
from .paged import (PagedKVCache, PrefixCache, cache_decode_step,
                    cache_prefill, cache_prefill_shared,
                    cache_verify_window)

_NO_SPAN = contextlib.nullcontext()     # _first_call's, after the first


@contextlib.contextmanager
def _first_call_span():
    """``serving.compile`` round a program's first call, and then
    ``gc.freeze()``: what tracing and compiling a program leaves behind
    (jaxprs, executables, their caches: hundreds of thousands of objects a
    cell) lives as long as the engine, and the collector's next full pass
    would walk all of it again in the middle of serving: with 19 programs
    warmed, one engine step of four in ten runs took 2-3 s longer
    (PERF.md section 6, PR 31). Frozen objects are still freed by their
    reference counts; only cycles among them would stay."""
    with _trace.span("serving.compile"):
        yield
    gc.freeze()

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
                 "keys", "preemptions", "ng", "ng_n")

    def __init__(self, req: Request, keys: np.ndarray):
        self.req = req
        self.kv_len = 0          # KV positions written (prompt + decoded)
        self.gen = 0             # tokens sampled so far
        self.tokens: List[int] = []
        self.pending = 0         # last sampled token (KV not yet written)
        self.done = False
        self.keys = keys         # [max_new, 2] uint32 sampling keys
        self.preemptions = 0
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
        # rows x width the prefill programs computed over, padding and
        # dummy rows included: tokens_prefilled over it is how full the
        # power-of-two grid was
        self.prefill_grid_tokens = 0
        self.tokens_discarded = 0    # thrown away by preemption recompute
        self.peak_pages_in_use = 0
        # rows of recurrent state (a family that keeps one; else all 0)
        self.state_rows_in_use = 0
        self.peak_state_rows_in_use = 0
        self.state_rows_assigned = 0     # rows handed out, re-admissions too
        # a family that routes (all 0 otherwise), summed over decode steps
        # and layers: picks routed (the whole slot grid, k a row), experts
        # held here that at least one pick named, picks the busiest held
        # expert took; and of the picks, those held here (computed) and
        # those held elsewhere (another chip's share of the experts)
        self.expert_rows = 0
        self.expert_reads = 0
        self.expert_rows_busiest = 0
        self.expert_picks_held = 0
        self.expert_picks_elsewhere = 0
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
                "tokens_decoded": self.tokens_decoded,
                "tokens_prefilled": self.tokens_prefilled,
                "prefill_grid_tokens": self.prefill_grid_tokens,
                "tokens_discarded": self.tokens_discarded,
                "peak_pages_in_use": self.peak_pages_in_use,
                "state_rows_in_use": self.state_rows_in_use,
                "peak_state_rows_in_use": self.peak_state_rows_in_use,
                "state_rows_assigned": self.state_rows_assigned,
                "expert_rows": self.expert_rows,
                "expert_reads": self.expert_reads,
                "expert_rows_busiest": self.expert_rows_busiest,
                "expert_picks_held": self.expert_picks_held,
                "expert_picks_elsewhere": self.expert_picks_elsewhere,
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
    the slots' row table (None where the family keeps no state). The last
    item is the expert every slot's token took, [chunk, layers, slots]
    (``[.., k]`` where a token takes k), int8 (int16 past 127 experts),
    from a family that routes (None from the others)."""
    pick = jnp.int8 if getattr(config, "num_experts", 0) <= 127 \
        else jnp.int16

    def body(carry, key_t):
        cache, tok, kvl, done, gen = carry
        n = jnp.where(done, 0, kvl + 1)
        cache, logits, picks = cache_decode_step(
            family, params, cache, block_tables, n, tok, config, state_rows,
            routes=True)
        kvl = jnp.where(done, kvl, kvl + 1)
        nxt = _sample_rows(logits, temps, key_t, sampled)
        emitted = jnp.where(done, -1, nxt)
        gen = gen + jnp.where(done, 0, 1)
        hit_eos = (~done) & (nxt == eos)
        done = done | hit_eos | (gen >= max_new)
        tok = jnp.where(emitted >= 0, nxt, tok)
        return (cache, tok, kvl, done, gen), (
            emitted, None if picks is None else picks[:, :, 0].astype(pick))

    (cache, tok, kvl, done, gen), (emitted, picks) = jax.lax.scan(
        body, (cache, tokens, kv_len, done, gen), keys, length=chunk)
    return cache, tok, kvl, done, gen, emitted, picks


class ServingEngine:
    """Continuous-batching decode over a paged KV cache.

    ``family`` is a model module exposing the decoder seam
    (models.llama / models.moe; models.falcon_h1, which also keeps a
    recurrent state a sequence beside the pages; models.phi4flash, whose
    declared stack keeps pages of one layer, rings and states;
    models.zaya, which keeps two convolutions' tails a sequence and says
    which expert each token took);
    ``params`` may be the
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
        layout = getattr(family, "pool_layout", lambda c: None)(config)
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
        if layout is not None and len(layout) > 3:
            # a latent pool: one row a token that keys and values share
            for on, what, missing in (
                    (self._prefix_on, "serving_prefix_cache",
                     "a prefix cache over latent pages (the shared "
                     "prefill's gather and decompression of the rows)"),
                    (self._spec_decode, "serving_spec_decode",
                     "a verify window over latent pages"),
                    (self._kv_quant, "serving_kv_quant",
                     "an int8 form of the latent row")):
                E.enforce(not on, f"FLAGS_{what} with {family.__name__}: "
                          f"its pool is one latent row a token, and "
                          f"{missing} is not written",
                          error=E.UnimplementedError)
        self.cache = PagedKVCache(
            config, num_pages, self.page_size, self.max_pages_per_seq,
            kv_dtype, kv_quant=self._kv_quant,
            state_shapes=shapes(config) if self._recurrent else None,
            state_rows=self.num_slots,
            pool_layout=layout)
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
        self._picks = None       # a routing family's chunk: not yet counted
        self._joins = set()      # group sizes whose join is compiled
        self._zero_rows = {}     # a greedy prefill group's temp and key, by g
        self._sampled = False
        self._zero_keys = {
            c: jnp.zeros((c, self.num_slots, 2), jnp.uint32)
            for c in (self.decode_chunk, self.turbo_chunk)}
        self._acct = EngineAccounting(self)

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
            raise self._finish(req, None, "rejected", reason, entered=False)
        # the scheduler consumes the NORMALIZED values it was screened
        # on — the original coercible-but-wrong-typed fields must not
        # ride into the loop
        (req.prompt, req.max_new_tokens, req.temperature,
         req.tenant, req.priority, req.deadline_s) = norm
        if getattr(req, "_submitted", False):
            # re-admission of a previously-submitted object (the client
            # kept it): per-run mutable state must not carry over — a
            # stale deadline anchor must not expire the new run, the
            # preemption count is the new run's, and the accounting
            # starts afresh at ``submitted`` below. (Preemption
            # re-queues re-enter via appendleft, not submit, and
            # deliberately keep all of it — the record follows the
            # request across ONE run.) The PRNG key is the exception:
            # _keys_for pinned the first run's key onto req.key, so a
            # resubmission replays byte-identical tokens.
            req._t_deadline = None
            req._preempt_count = 0
        # overload gates, in severity order: a draining replica refuses
        # everything; an SLO fast-burn sheds best-effort work; a full
        # bounded queue sheds (or displaces for higher priority). All
        # three raise BEFORE the request touches any engine state.
        if self._draining:
            raise self._finish(req, None, "shed", "engine is draining",
                               entered=False)
        if (self._shed_on_burn and req.priority <= 0
                and self._acct.burning()):
            raise self._finish(req, None, "shed", "SLO fast-burn alerting; "
                               "priority<=0 work shed", entered=False)
        if self._max_queue and len(self.queue) >= self._max_queue:
            victim = self._displaceable_pos(req.priority)
            if victim is None:
                raise self._finish(
                    req, None, "shed", f"queue full ({self._max_queue}) "
                    f"and no lower-priority request to displace",
                    entered=False)
            shed = self.queue[victim]
            del self.queue[victim]
            self._acct.displaced(shed, req, self._max_queue)
            self._finish(shed, None, "shed", "displaced by "
                         f"higher-priority request {req.rid!r}")
        if req.deadline_s is not None:
            req._t_deadline = time.perf_counter() + req.deadline_s
            self._deadlines_seen = True
        req._submitted = True
        if (self._acct.journal is not None and req.temperature > 0.0
                and req.key is None):
            # a journaled request's sampling key is pinned BEFORE its
            # record is written, so a re-dispatch replays byte-identical
            # tokens
            self._rng_fallback += 1
            req.key = jax.random.PRNGKey(self._rng_fallback)
        self._acct.submitted(req)
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
        return self._acct.autoscale_payload()

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
        return self._acct.publish_frames(
            name, dir_path, client=client, local_only=local_only,
            min_interval_s=min_interval_s, slo_fn=slo_fn)

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
        return self._acct.attach_journal(name, dir_path, client) \
            if self._failover else None

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

    def _finish(self, req: Request, idx: Optional[int], state: str,
                reason: Optional[str] = None, *, entered: bool = True):
        """The ONE way a request ends: ``completed`` (from slot
        ``idx``), ``expired`` (from its slot with the tokens it had —
        they were sampled and are the client's to keep, so the
        generated-discarded==emitted token contract holds — or from the
        queue with none), ``shed`` by overload policy, or ``rejected``
        as malformed. A request that ``entered`` the engine leaves
        through ``outputs`` — never silently dropped (its submitter
        already returned from ``submit``); for one refused at ``submit``
        (``entered`` False: it touched no engine state) the typed error
        comes back for ``submit`` to raise, a shed one's with the
        demand-model backoff hint."""
        slot = None
        if idx is not None:
            slot = self.slots[idx]
            self.slots[idx] = None
            self._state_dirty = self._bt_dirty = True
        hint = self._acct.retry_after() if state == "shed" else None
        # before the free: the final page-seconds tick reads the pages
        cost = self._acct.finished(req, slot, idx, state, reason, hint,
                                   entered)
        if state != "rejected":
            setattr(self.stats, state, getattr(self.stats, state) + 1)
        if not entered:
            return RequestRejected(req.rid, reason) if hint is None \
                else EngineOverloaded(req.rid, reason, hint)
        tokens = np.zeros(0, np.int32)
        if slot is not None:
            tokens = np.asarray(slot.tokens, np.int32)
            if (state == "completed" and self._prefix is not None
                    and slot.kv_len >= self.page_size):
                # retirement insertion: only COMMITTED positions enter the
                # radix — the prompt plus the generated tokens whose KV is
                # already written (kv_len worth; the final pending token's
                # KV never was). insert() takes a cache hold on each newly
                # shared page BEFORE the free below, so the pages survive
                # the sequence's release with ref >= 1.
                prompt = np.asarray(req.prompt, np.int32)
                gen_committed = slot.kv_len - int(prompt.shape[0])
                stream = prompt if gen_committed <= 0 else np.concatenate(
                    [prompt, tokens[:gen_committed]])
                self._prefix.insert(stream,
                                    self.cache.alloc.seq_pages(req.rid))
            self.cache.alloc.free(req.rid)
        self.outputs[req.rid] = RequestOutput(
            rid=req.rid, tokens=tokens,
            prompt_len=int(np.asarray(req.prompt).shape[0]),
            preemptions=slot.preemptions if slot is not None
            else getattr(req, "_preempt_count", 0),
            tenant=getattr(req, "tenant", "default"), cost=cost,
            finish_reason=state, retry_after_s=hint,
            shed_reason=reason if state == "shed" else None)

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
        self._acct.drain_begun(again=already)
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
                    self._finish(r, None, "shed", "engine is draining")
            self.queue = keep
        self._acct.drain_queue_shed(again=already)

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
                    self._finish(r, None, "expired")
                else:
                    keep.append(r)
            self.queue = keep
        for idx in range(self.num_slots):
            slot = self.slots[idx]
            if slot is None or slot.done:
                continue
            t = getattr(slot.req, "_t_deadline", None)
            if t is not None and now >= t:
                self._finish(slot.req, idx, "expired")

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
        site: what lies on Python's frame stack above a jitted call is
        not free while the program is traced (the module's docstring)."""
        if id(fn) in self._called:
            return _NO_SPAN
        self._called.add(id(fn))
        return _first_call_span()

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
            self._acct.prefix("evictions", dropped)
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
        self._acct.prefix("lookups")
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
                self._acct.prefix("hits")
                self._acct.prefix("tokens_saved", cached)
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

    def _preempt_victim_idx(self) -> Optional[int]:
        """Pick the eviction victim. Default: the YOUNGEST live request
        (highest slot index — the original recompute policy). With
        ``slo_preemption`` on: the request with the LOWEST eviction
        cost, ordered by (priority, prior preemptions, accumulated
        work) — evict the least important class first; within a class
        protect repeat victims (anti-starvation) and then evict the
        request that is cheapest to recompute (the accounting's
        ``work_done``: the cost record's tokens when the monitor keeps
        one, else the current run's KV length)."""
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
            key = (getattr(slot.req, "priority", 0), slot.preemptions,
                   self._acct.work_done(slot), -idx)   # tie-break: youngest
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
        # before the free: the evicted request PAID for the pages
        self._acct.preempted(slot, idx, "slo" if self._slo_preemption
                             else "youngest")
        self.cache.alloc.free(slot.req.rid)
        slot.req._preempt_count = getattr(
            slot.req, "_preempt_count", 0) + 1
        self.queue.appendleft(slot.req)
        self.stats.preempted += 1
        # the evicted request's sampled-but-unretired tokens are
        # recomputed from scratch: move them to the discarded
        # column so generated - discarded stays == emitted
        self.stats.tokens_discarded += slot.gen
        return True

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
                self._acct.deferred(self.queue[0], "no_free_slot",
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
                self._acct.deferred(req, "watermark",
                            free_slack=self._free_slack(), need=need,
                            watermark_pages=self.watermark_pages,
                            queue_depth=len(self.queue))
                break
            self.queue.popleft()
            if self._alloc_for(req, s_pad) is None:
                self.queue.appendleft(req)
                self._acct.deferred(req, "alloc_failed", need=need,
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
                self._acct.deferred(self.queue[0], "no_free_slot",
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
                self._acct.deferred(self.queue[0], "tenant_cap", cap=cap,
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
                self._acct.deferred(req, "watermark",
                            free_slack=self._free_slack(), need=need,
                            watermark_pages=self.watermark_pages,
                            queue_depth=len(self.queue))
                break
            del self.queue[pos]
            if self._alloc_for(req, s_pad) is None:
                self.queue.insert(pos, req)
                self._acct.deferred(req, "alloc_failed", need=need,
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
        n, page, B = len(group), self.page_size, self.num_slots
        cache, stats, rids = self.cache, self.stats, [r.rid for r in group]
        alloc, sentinel = cache.alloc, cache.num_pages  # a dummy row's page
        need = s_pad // page
        # with the prefix cache on, every member of this group shares
        # the same cached page-aligned prefix length (admission grouped
        # by it): the program prefills only the uncached tail, reading
        # the shared context pages without ever writing them
        cached = int(getattr(group[0], "_pfx_cached", 0)) \
            if self._prefix is not None else 0
        self._acct.admitted(group, s_pad, cached, len(free))
        with _trace.span("serving.prefill", group=n, s_pad=s_pad):
            with _trace.span("serving.prefill.build"):
                g = 1
                while g < n:
                    g *= 2
                ncp = cached // page
                s_eff = s_pad - cached
                need_eff = need - ncp
                ids = np.zeros((g, s_eff), np.int32)
                rows = np.full((g, need_eff), sentinel, np.int32)
                ctx_rows = np.full((g, ncp), sentinel, np.int32)
                slen = np.ones(g, np.int32)
                temps = np.zeros(g, np.float32)
                keys = np.zeros((g, 2), np.uint32)
                slots = []
                for j, r in enumerate(group):
                    prompt = np.asarray(r.prompt, np.int32)
                    plen = int(prompt.shape[0])
                    tail = plen - cached
                    ids[j, :tail] = prompt[cached:]
                    brow = alloc.block_row(rids[j], need)
                    ctx_rows[j] = brow[:ncp]
                    rows[j] = brow[ncp:]
                    slen[j] = tail
                    temps[j] = r.temperature
                    slot = _Slot(r, self._keys_for(r))
                    slot.kv_len = plen
                    slot.preemptions = getattr(r, "_preempt_count", 0)
                    keys[j] = slot.keys[0]
                    slots.append(slot)
                sampled = any(r.temperature > 0 for r in group)
                # the program's name; its tail is what it is specialized on
                spec_key = ("serving.prefill_shared", g, s_eff, ncp, sampled) \
                    if cached else ("serving.prefill", g, s_pad, sampled)
                pf = (self._prefill_shared_fn if cached
                      else self._prefill_fn)(*spec_key[1:])
                up = dict(ids=ids, page_rows=rows, slen=slen)
                # Where no request of the group can end on its first token
                # (none names an EOS), nothing the scheduler decides before
                # the next chunk's dispatch reads that token: it goes to its
                # slot on the device (_join_first), the chunk's inputs are
                # built while the device prefills, the chunk starts where the
                # prefill ends, and the host reads the token after that.
                later = all(r.eos_token_id is None for r in group)
                if later:
                    where = up["at"] = np.full(g, B, np.int32)
                    where[:n] = free[:n]
                    if g not in self._joins:
                        # compiled here, beside the group's prefill program,
                        # not at a first join in the middle of serving
                        self._joins.add(g)
                        with _trace.span("serving.compile"):
                            self._join(*jax.device_put((
                                np.zeros(B, np.int32), where,
                                np.zeros(g, np.int32))))
                if sampled:
                    up.update(temp=temps, key=keys)
                if cached:
                    up["ctx_rows"] = ctx_rows
                if self._recurrent:
                    with _trace.span("serving.step.state"):
                        # each request's own row; a dummy names nobody's
                        up["state_rows"] = cache.state_row_table(
                            rids + [None] * (g - n))
                pf_kwargs = jax.device_put(up)       # one call for them all
                if not sampled:     # greedy: neither is read, nor sent again
                    if g not in self._zero_rows:
                        self._zero_rows[g] = jax.device_put(
                            dict(temp=temps, key=keys))
                    pf_kwargs.update(self._zero_rows[g])
                at = pf_kwargs.pop("at", None)
                pf_args = (self.params, pf_kwargs.pop("ids"), cache.pool)
            run = self._acct.dispatching(spec_key, pf, pf_args, pf_kwargs,
                                         (2,), n)
            with _trace.span("serving.prefill.dispatch", rows=g, width=s_eff,
                             tokens=int(slen[:n].sum())), \
                    self._first_call(pf):
                cache.pool, tok_a = pf(*pf_args, **pf_kwargs)
            stats.prefill_grid_tokens += g * s_eff
            # the slots are taken now, with all that no token decides
            for j, (r, slot) in enumerate(zip(group, slots)):
                tail = int(slen[j])
                alloc.advance(rids[j], tail + cached)
                slot.gen = 1
                slot.done = slot.gen >= r.max_new_tokens
                self.slots[free[j]] = slot
                stats.admitted += 1
                stats.tokens_generated += 1
                stats.tokens_prefilled += tail
            self._state_dirty = self._bt_dirty = True

            def first_tokens():
                with _trace.span("serving.prefill.fetch"):
                    # the np.asarray download syncs the device — the span
                    # ends (and TTFT is stamped) when the first token actually
                    # EXISTS on the host, not when the dispatch returned
                    toks = np.asarray(tok_a)
                self._acct.downloaded(run, kv_sample=False)
                self._acct.first_tokens(run, group, s_eff, cached)
                with _trace.span("serving.prefill.emit"):
                    for j, (r, slot) in enumerate(zip(group, slots)):
                        tok = int(toks[j])
                        slot.tokens.append(tok)
                        slot.pending = tok
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
                  .dispatch               the jitted call (rows, width:
                                          the grid it computes over;
                                          tokens: the real ones in it)
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
                        self._finish(self.slots[idx].req, idx, "completed")
            with _trace.span("serving.step.compact"):
                self._compact()
            with _trace.span("serving.step.admit"):
                self._admit()
            in_use = self.cache.alloc.used_pages
            self.stats.peak_pages_in_use = max(
                self.stats.peak_pages_in_use, in_use)
            if self._recurrent:
                st, alloc = self.stats, self.cache.alloc
                st.state_rows_in_use = alloc.used_rows
                st.peak_state_rows_in_use = max(st.peak_state_rows_in_use,
                                                alloc.used_rows)
                st.state_rows_assigned = alloc.rows_assigned

            live_idx = [i for i, s in enumerate(self.slots)
                        if s is not None and not s.done]
            self._acct.tick(len(live_idx), in_use)
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

        d, cache, sampled = self._dev, self.cache, self._sampled
        ck = self._chunk_fns[(C, sampled)]
        ck_args = (self.params, cache.pool, d["bt"], d.get("rows"),
                   d["tokens"], d["kv_len"], d["done"], d["gen"], keys,
                   d["temps"], d["max_new"], d["eos"])
        run = self._acct.dispatching(("serving.decode_chunk", C, sampled),
                                     ck, ck_args, None, (1,), len(live_idx))
        with _trace.span("serving.decode_chunk.dispatch"), \
                self._first_call(ck):
            (cache.pool, tok, kvl, done_a, gen_a, emitted,
             self._picks) = ck(*ck_args)
        self._dev.update(tokens=tok, kv_len=kvl, done=done_a, gen=gen_a)
        self._first_tokens()     # the prefills are done before the chunk is
        with _trace.span("serving.decode_chunk.fetch"):
            # ONE device->host transfer per chunk: every host-side fact
            # is derivable from the emitted grid (-1 = slot was done at
            # that step; a write and a sample happen exactly on non -1
            # steps). The download syncs, so the span's end — and the
            # accounting's stamp in ``downloaded`` — is when the tokens
            # reached the host.
            emitted = np.asarray(emitted)                # [C, B]
        self._acct.downloaded(run)
        with _trace.span("serving.decode_chunk.emit"):
            if self._picks is not None:
                self._count_picks()
            counts = []
            cols = emitted.T.tolist()        # a slot's steps, a row each
            whole = bool((emitted >= 0).all())
            advance = cache.alloc.advance
            for i in live_idx:
                s = self.slots[i]
                req, eos = s.req, s.req.eos_token_id
                toks = cols[i] if whole else [t for t in cols[i] if t >= 0]
                n = len(toks)
                counts.append(n)
                if n:
                    s.tokens.extend(toks)
                    advance(req.rid, n)
                    s.kv_len += n
                    s.gen += n
                    s.pending = toks[-1]
                s.done = s.gen >= req.max_new_tokens or (
                    eos is not None and n > 0 and toks[-1] == eos)
        self._chunk_done(run, live_idx, C, counts)
        return True

    def _count_picks(self):
        """A chunk's picks ([steps, layers, slots(, k)]: the experts each
        slot's row took, every slot of the grid, as the program routed
        them) into the stats' sums. A config that holds a share of the
        experts (``held``: [first, end)) counts reads among those."""
        with _trace.span("serving.decode_chunk.routes"):
            picks = np.asarray(self._picks)
            self._picks = None
            E = self.config.num_experts
            lo, hi = getattr(self.config, "held", (0, E))
            # one count of every expert a (step, layer): a bincount over
            # the picks offset by their (step, layer), not a comparison
            # of every pick with every expert (256 x 8 picks against 12
            # experts held was 2.5% of A.X K1's traced slice, PR 37)
            flat = picks.reshape(-1, picks[0, 0].size).astype(np.int64)
            n = flat.shape[0]
            took = np.bincount((flat + E * np.arange(n)[:, None]).ravel(),
                               minlength=n * E).reshape(n, E)[:, lo:hi]
            held = int(took.sum())
            self.stats.expert_rows += picks.size
            self.stats.expert_reads += int((took > 0).sum())
            self.stats.expert_rows_busiest += int(took.max(-1).sum())
            self.stats.expert_picks_held += held
            self.stats.expert_picks_elsewhere += picks.size - held

    def _chunk_done(self, run, live_idx: List[int], C: int, counts,
                    accepted=None):
        """A chunk's (or a verify window's) ``C`` steps into the stats,
        and its one hand-over to the accounting, outside the per-slot
        loop: the live slots, the chunk length, each slot's tokens."""
        new_tokens = sum(counts)
        self.stats.decode_steps += C
        self.stats.tokens_generated += new_tokens
        self.stats.tokens_decoded += new_tokens
        self.stats._occ_steps += C * self.num_slots
        if accepted is not None:
            self.stats.spec_rounds += len(live_idx)
            self.stats.spec_drafted += (C - 1) * len(live_idx)
            self.stats.spec_accepted += sum(accepted)
        self._acct.chunk_done(run, [self.slots[i] for i in live_idx], C,
                              counts, accepted)

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
            B, dev = self.num_slots, self._dev
            if self._bt_dirty:
                dev["bt"] = jnp.asarray(self._block_tables(live_idx))
                self._bt_dirty = False
            drafts = np.zeros((B, C), np.int32)
            kv_len = np.zeros(B, np.int32)
            live_m = np.zeros(B, bool)
            for i in live_idx:
                s = self.slots[i]
                drafts[i] = self._draft_for(s, C)
                kv_len[i] = s.kv_len
                live_m[i] = True
            vf, cache = self._spec_fn(C), self.cache
            vf_args = (self.params, cache.pool, dev["bt"],
                       jnp.asarray(drafts), jnp.asarray(kv_len),
                       jnp.asarray(live_m))
        run = self._acct.dispatching(("serving.spec_chunk", C), vf, vf_args,
                                     None, (1,), len(live_idx))
        with _trace.span("serving.spec_chunk.dispatch"), \
                self._first_call(vf):
            cache.pool, preds_a = vf(*vf_args)
        with _trace.span("serving.spec_chunk.fetch"):
            preds = np.asarray(preds_a)                  # [B, C]
        self._acct.downloaded(run)
        with _trace.span("serving.spec_chunk.emit"):
            counts, accepted = [], []
            advance = cache.alloc.advance
            for i in live_idx:
                s = self.slots[i]
                req = s.req
                dr = drafts[i]
                col = preds[i]
                a = 0
                while a < C - 1 and dr[a + 1] == col[a]:
                    a += 1
                emitted = [int(t) for t in col[:a + 1]]
                n = a + 1
                s.tokens.extend(emitted)
                counts.append(n)
                accepted.append(a)
                advance(req.rid, n)
                s.kv_len += n
                s.gen += n
                s.pending = emitted[-1]
                # turbo preconditions rule out EOS; only the length bound
                # can finish a sequence here
                s.done = s.gen >= req.max_new_tokens
        self._chunk_done(run, live_idx, C, counts, accepted)
        # the device-side sequential slot state is stale after a spec
        # round (tokens/kv_len/gen advanced on the host): rebuild it
        # before the next sequential chunk
        self._state_dirty = True
        return True

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
