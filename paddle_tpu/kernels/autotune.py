"""Runtime kernel autotuning with a persisted cache.

Reference capability: paddle/phi/kernels/autotune/{cache.h,cache_base.h,
switch_autotune.h} — measure candidate algorithms for an op at its actual
runtime shape once, remember the winner keyed by shape/dtype, persist
across processes. There the candidates are cuDNN algos; here they are
Pallas block sizes for the flash-attention kernels (the one knob Mosaic
does not pick for us — XLA autotunes its own fusions already).

TPU-native design:
- Tuning happens at DISPATCH time (trace time): shapes are static under
  jit, so the dispatcher knows the exact (bh, sq, sk, d, dtype, causal)
  the kernel will run at. Candidates are timed with standalone jitted
  fwd+bwd runs on freshly materialised random inputs — real compiles of
  the real kernel at the real shape.
- The winner is cached in-process AND in a JSON file (the tracked
  ``autotune_cache.json`` at the root of the checkout, override via
  PADDLE_TPU_AUTOTUNE_CACHE) so later processes — chip_smoke.py and the
  bench in their never-measure "cached" mode — skip straight to the
  tuned blocks, and those blocks are a function of the committed tree.
  Writes are atomic (tmp + rename).
- Measurement only runs on a real TPU backend (timing interpret-mode
  pallas on CPU is meaningless); elsewhere the defaults return
  immediately. FLAGS use_autotune=False (or env PADDLE_TPU_AUTOTUNE=0)
  freezes everything at the defaults. The dense flash kernels' default
  is no constant but a rule over the call's shape
  (tiling.flash_blocks_for), so no tracked file has to foresee a shape.
- A sweep in which NO candidate compiles raises with the compiler's
  message: a kernel that cannot run at a shape its ``supported()`` gate
  admitted is a bug to fix at the gate, not a default to run on.
"""
from __future__ import annotations

import json
import math
import os
import re
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import monitor as _monitor
from ..core import flags as _flags
from ..core.compile_cache import CHECKOUT

_flags.define_flag("use_autotune", True,
                   "Measure+cache pallas kernel block sizes per shape "
                   "(reference: phi/kernels/autotune).")

# The segment (packed) kernels' default and sweep set. The dense kernels'
# default is a rule over the shape (tiling.flash_blocks_for) and their sweep
# set the rule's answer first, so a timing tie keeps it, then CANDIDATES:
# a measuring run can still beat the rule.
DEFAULT_BLOCKS = (128, 128)
VARLEN_CANDIDATES = ((128, 128), (256, 128), (128, 256), (256, 256),
                     (512, 128), (512, 256))
CANDIDATES = VARLEN_CANDIDATES + (
    (256, 512), (512, 512), (1024, 256), (256, 1024), (1024, 512),
    (512, 1024), (1024, 1024), (512, 2048))


def _cache_path() -> str:
    return os.environ.get(
        "PADDLE_TPU_AUTOTUNE_CACHE",
        os.path.join(CHECKOUT, "autotune_cache.json"))


class AutotuneCache:
    """shape-key -> chosen config, in-memory with JSON persistence."""

    def __init__(self, path: Optional[str] = None):
        self._explicit_path = path
        self._mem: dict = {}
        self._loaded = False
        self._resolved_path: Optional[str] = None

    @property
    def _path(self) -> str:
        # Resolved lazily, NOT in __init__: the module-level _CACHE is
        # constructed at import time, which may precede a harness
        # setting PADDLE_TPU_AUTOTUNE_CACHE.
        if self._explicit_path is not None:
            return self._explicit_path
        return _cache_path()

    def _load(self):
        # PADDLE_TPU_AUTOTUNE_CACHE may change AFTER the first load: a
        # stale sticky _loaded would keep serving old-path entries and
        # put() would write their union into the new file (cross-cache
        # contamination, ADVICE r5). Track the last-resolved path and
        # evict when it moves.
        path = self._path
        if self._resolved_path is not None and path != self._resolved_path:
            _monitor.inc("autotune.cache.evictions", len(self._mem),
                         doc="entries dropped on cache-path change")
            self._mem.clear()
            self._loaded = False
        self._resolved_path = path
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self._path) as f:
                disk = json.load(f)
            if isinstance(disk, dict):
                # disk entries never override fresher in-memory ones
                for k, v in disk.items():
                    self._mem.setdefault(k, v)
        except (OSError, ValueError):
            pass

    def get(self, key: str):
        self._load()
        return self._mem.get(key)

    def get_nearest(self, key: str):
        """Warm-start lookup for a cold shape key: the closest tuned
        entry whose key shares this key's non-numeric skeleton (same
        knob family, backend, dtype — digit runs wildcarded), by
        log-space distance over the numeric fields. A serving shape
        that was never swept (new batch size, new max_len) then seeds
        from its nearest tuned neighbor instead of the hardcoded
        default. Returns ``(neighbor_key, value)`` or ``None``."""
        self._load()
        skel = re.sub(r"\d+", "#", key)
        nums = [int(x) for x in re.findall(r"\d+", key)]
        best = None
        best_d = None
        for k in sorted(self._mem):       # deterministic tie-break
            v = self._mem[k]
            if k == key or not isinstance(v, dict):
                continue
            if re.sub(r"\d+", "#", k) != skel:
                continue
            kn = [int(x) for x in re.findall(r"\d+", k)]
            if len(kn) != len(nums):
                continue
            d = sum(abs(math.log(a + 1) - math.log(b + 1))
                    for a, b in zip(nums, kn))
            if best_d is None or d < best_d:
                best_d, best = d, (k, v)
        return best

    def put(self, key: str, value: dict):
        self._load()
        self._mem[key] = value
        try:
            # re-merge the file first: a concurrent process may have
            # written other shapes since our load — don't erase them
            # (our own fresh entries win on conflict)
            try:
                with open(self._path) as f:
                    disk = json.load(f)
                if isinstance(disk, dict):
                    for k, v in disk.items():
                        self._mem.setdefault(k, v)
            except (OSError, ValueError):
                pass
            os.makedirs(os.path.dirname(self._path), exist_ok=True)
            tmp = f"{self._path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(self._mem, f, indent=1, sort_keys=True)
            os.replace(tmp, self._path)
        except OSError:
            pass   # cache is an optimisation; never fail the op

    def clear(self):
        self._mem.clear()
        self._loaded = True


_CACHE = AutotuneCache()

# What flash_blocks actually RETURNED in this process, per shape key —
# the benchmark's evidence of which blocks the traced program used
# (distinct from the persisted cache, which holds every shape any prior
# run tuned).
_USED: dict = {}


def used_blocks() -> dict:
    """{shape_key: {"blocks": [bq, bk], "source": cache|measured|default}}
    for every dispatch decision made by this process."""
    return dict(_USED)


def record_rule(key: str, **fields) -> None:
    """Record in ``used_blocks()`` what a kernel took from its call's
    shape alone, with no knob to tune (source ``shape-rule``)."""
    _USED[key] = dict(fields, source="shape-rule")


def _mode() -> str:
    """PADDLE_TPU_AUTOTUNE: "1" measure+cache (default), "cached" use
    cache hits but never measure (chip_smoke.py and the bench: their
    blocks are a function of the committed cache file), "0" off."""
    return os.environ.get("PADDLE_TPU_AUTOTUNE", "1")


def _dividing(among, sq, sk):
    """``among`` clamped to the sequences, those that divide them."""
    out = []
    for bq, bk in among:
        c = (min(bq, sq), min(bk, sk))
        if c not in out and not (sq % c[0] or sk % c[1]
                                 or c[0] % 8 or c[1] % 8):
            out.append(c)
    return out


def flash_candidates(bh, sq, sk, d, dtype):
    """Legal (block_q, block_k) candidates for a dense flash shape, the
    shape rule's answer first."""
    from .tiling import (FLASH_VMEM_BUDGET, flash_blocks_for,
                         flash_specs_legal, flash_vmem_bytes)

    rule = flash_blocks_for(sq, sk, d, dtype)
    return [rule] + [
        c for c in _dividing(CANDIDATES, sq, sk)
        if c != rule
        and flash_vmem_bytes(sq, sk, d, *c, dtype) <= FLASH_VMEM_BUDGET
        and flash_specs_legal(bh, sq, sk, d, *c, dtype)]


def _rand(rng, shape, dtype, scale=1.0):
    # float32 host generation: float64 standard_normal doubles the host
    # bytes for multi-GB sweep operands for no measurement benefit
    return jnp.asarray(
        rng.standard_normal(shape, dtype=np.float32) * scale, dtype)


def _flash_measurer(b, sq, sk, h, kvh, d, dtype, causal):
    """Per-sweep measurement closure: operands materialise ONCE, every
    candidate reuses them (per-candidate regeneration cost minutes of
    host RNG + transfer on large shapes)."""
    # Import from the submodule directly: the package __init__ rebinds
    # the ``flash_attention`` attribute to the function, so a lazy
    # ``from . import flash_attention`` here would get the function.
    from .flash_attention import flash_attention as _flash

    rng = np.random.default_rng(0)
    q = _rand(rng, (b, sq, h, d), dtype)
    k = _rand(rng, (b, sk, kvh, d), dtype)
    v = _rand(rng, (b, sk, kvh, d), dtype)

    def measure(bq, bk, interpret=False):
        def loss(q, k, v):
            return jnp.sum(_flash(
                q, k, v, causal=causal, block_q=bq, block_k=bk,
                interpret=interpret).astype(jnp.float32))

        return _best_of_3(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                          q, k, v)

    return measure


def _best_of_3(f, *args) -> float:
    """Compile + warm up ``f``, then the best wall time of three calls,
    each ended by ``block_until_ready`` on every output."""
    jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _measure_flash(b, sq, sk, h, kvh, d, dtype, causal, bq, bk,
                   interpret=False) -> float:
    """One-shot measurement (tests); sweeps use _flash_measurer."""
    return _flash_measurer(b, sq, sk, h, kvh, d, dtype, causal)(
        bq, bk, interpret=interpret)


def _tuning_backend() -> bool:
    return jax.default_backend() == "tpu"


def _in_trace() -> bool:
    """True when called under an ambient jax trace (jit/grad/vmap).

    Measurement is impossible there: a jitted candidate invoked while an
    outer trace is active gets STAGED into that trace, so its outputs
    are tracers and nothing can be timed. Dispatches under jit use the
    cache or the defaults; real sweeps run from eager dispatch sites or
    explicit pre-tuning (scripts/tpu_smoke.py)."""
    from jax._src import core as _core
    return not _core.trace_state_clean()


def _tuned(key, field, default, candidates, measure, make_measure, cache,
           label=str, warm_start=False, default_source="default"):
    """The one tuning policy behind every knob (flash/varlen blocks, CE
    chunk, page size): returns the value to use for ``key`` and records
    it with its ``source`` in ``used_blocks()``.

    off -> ``default``; cache hit -> the hit; then, wherever measuring
    is impossible (not a TPU, "cached" mode, under a trace) ->
    ``default`` — or, with ``warm_start``, the nearest tuned neighbour
    that is one of ``candidates()``; else sweep ``candidates()`` with
    ``measure`` (``make_measure()`` builds the real one lazily — it
    materialises operands), persist the winner and return it. Failing
    candidates drop out; if ALL fail the last compiler message is
    raised. ``label`` names a candidate in the persisted timings,
    ``default_source`` what ``default`` is in ``used_blocks()``."""
    def stored(value):              # block pairs persist as JSON lists
        return list(value) if isinstance(value, tuple) else value

    def use(value, source):
        _USED[key] = {field: stored(value), "source": source}
        return value

    def cannot_measure(tag):
        nb = cache.get_nearest(key) if warm_start else None
        if nb and nb[1].get(field) in candidates():
            return use(nb[1][field], f"warm-start:{nb[0]}")
        return use(default, tag)

    mode = _mode()
    if not _flags.flag_value("use_autotune") or mode == "0":
        return use(default, "off")
    cache = cache or _CACHE
    if measure is None and mode != "cached" and not _tuning_backend():
        return cannot_measure(f"{default_source}-not-tpu")
    hit = cache.get(key)
    _monitor.inc("autotune.cache.hit" if hit else "autotune.cache.miss")
    if hit:
        value = hit[field]
        return use(tuple(value) if isinstance(value, list) else value,
                   "cache")
    if mode == "cached":
        return cannot_measure(default_source)
    if measure is None and _in_trace():
        return cannot_measure(f"{default_source}-in-trace")
    cands = candidates()
    if len(cands) == 1:
        cache.put(key, {field: stored(cands[0]), "us": None,
                        "candidates": 1})
        return use(cands[0], "measured")
    measure = measure or make_measure()
    _monitor.inc("autotune.sweeps", doc="candidate measurement sweeps run")
    timings = {}
    last_err = None
    for c in cands:
        try:
            timings[c] = measure(*c) if isinstance(c, tuple) else measure(c)
        except Exception as e:          # a failing candidate drops out
            last_err = e
    if not timings:
        raise RuntimeError(
            f"autotune {key}: none of the candidates {cands} compiled "
            f"and ran; last: {type(last_err).__name__}: {last_err}"
        ) from last_err
    best = min(timings, key=timings.get)
    cache.put(key, {field: stored(best),
                    "us": round(timings[best] * 1e6, 1),
                    "candidates": len(timings),
                    "timings_us": {label(c): round(t * 1e6, 1)
                                   for c, t in timings.items()}})
    return use(best, "measured")


def _blocks_label(c) -> str:
    return f"{c[0]}x{c[1]}"


def _default_blocks(sq, sk):
    return (min(DEFAULT_BLOCKS[0], sq), min(DEFAULT_BLOCKS[1], sk))


# --------------------------------------------------------------------------
# fused cross-entropy vocab-chunk tuning (same cache/policy machinery).
# The chunk trades scan length against per-chunk logits HBM: too small
# pays scan overhead, too large re-materialises what the kernel exists
# to avoid. Like cuDNN algo choice, the right point is measured, not
# guessed.
# --------------------------------------------------------------------------

CE_DEFAULT_CHUNK = 4096
CE_CANDIDATES = (1024, 2048, 4096, 8192, 16384)


def ce_candidates(vocab: int):
    """Legal vocab-chunk candidates, default first, clamped to V."""
    out = []
    for c in (CE_DEFAULT_CHUNK,) + CE_CANDIDATES:
        c = min(c, vocab)
        if c % 128 and c != vocab:   # keep lane-aligned tiles
            continue
        if c not in out:
            out.append(c)
    return out


def _ce_measurer(n, d, v, dtype):
    """Per-sweep closure: the [V, D] head (multi-GB at 100k vocab)
    materialises once, every candidate reuses it."""
    from .fused_ce import fused_cross_entropy

    rng = np.random.default_rng(0)
    x = _rand(rng, (n, d), dtype)
    head = _rand(rng, (v, d), dtype, scale=0.05)
    labels = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)

    def measure(chunk):
        return _best_of_3(jax.jit(jax.grad(
            lambda x, h: fused_cross_entropy(x, h, labels,
                                             vocab_chunk=chunk),
            argnums=(0, 1))), x, head)

    return measure


def _measure_ce(n, d, v, dtype, chunk) -> float:
    """One-shot measurement (tests); sweeps use _ce_measurer."""
    return _ce_measurer(n, d, v, dtype)(chunk)


def ce_chunk(n_tokens, hidden, vocab, dtype,
             default: int = CE_DEFAULT_CHUNK,
             measure: Optional[Callable] = None,
             cache: Optional[AutotuneCache] = None) -> int:
    """Tuned vocab_chunk for a fused-CE call; measures once per shape
    key and caches (memory + disk), same policy as flash_blocks
    (:func:`_tuned`)."""
    key = (f"ce:{jax.default_backend()}:{jnp.dtype(dtype).name}:"
           f"n{n_tokens}v{vocab}d{hidden}")
    return int(_tuned(
        key, "chunk", min(default, vocab), lambda: ce_candidates(vocab),
        measure, lambda: _ce_measurer(n_tokens, hidden, vocab, dtype),
        cache))


# --------------------------------------------------------------------------
# paged-attention page-size tuning (same cache/policy machinery). The page
# is the unit the pool allocates and the unit the decode kernel copies
# out of HBM (one DMA a page, all KV heads); what the kernel computes on
# is a block of pages whose size it derives itself from the shapes, so
# the page no longer sets its step count. Small pages waste less pool
# memory on ragged tails but cost more DMA descriptors per token; large
# pages amortise the descriptors but strand capacity. Like the flash
# blocks, the right point is measured on the real chip, not guessed.
# --------------------------------------------------------------------------

PAGED_DEFAULT_PAGE = 16
PAGED_CANDIDATES = (8, 16, 32, 64)


def paged_candidates(dtype, max_len: int, kv_quant: bool = False):
    """Legal page-size candidates for a pool dtype, default first; the
    packed-dtype sublane tile (16) floors bf16 pages. A quantized pool
    stores int8 codes whose sublane tile is 32 rows — smaller pages
    would force the kernel arm to fall back, so they are not offered."""
    sub = 32 if kv_quant else (16 if jnp.dtype(dtype).itemsize == 2
                               else 8)
    out = []
    for ps in (PAGED_DEFAULT_PAGE,) + PAGED_CANDIDATES:
        if ps < sub or ps > max(max_len, sub):
            continue
        if ps not in out:
            out.append(ps)
    return out or [max(sub, PAGED_DEFAULT_PAGE)]


def _paged_measurer(batch, nh, kvh, d, max_len, dtype, kv_quant=False):
    """Per-sweep closure: one random KV working set, re-paged per
    candidate (pool bytes are identical across candidates; ``max_len``
    rounds up to the largest candidate so every page size divides it).
    ``kv_quant`` measures the int8-page arm: codes + per-page scales,
    quantized from the same working set."""
    from .paged_attention import ragged_paged_attention

    cap = max(PAGED_CANDIDATES)
    max_len = -(-max_len // cap) * cap
    rng = np.random.default_rng(0)
    q = _rand(rng, (batch, nh, d), dtype)
    flat_k = _rand(rng, (batch * max_len, kvh, d), dtype)
    flat_v = _rand(rng, (batch * max_len, kvh, d), dtype)
    lengths = jnp.asarray(
        rng.integers(max_len // 4, max_len + 1, (batch,)), jnp.int32)

    def _quantize(pages_arr):
        s = jnp.max(jnp.abs(pages_arr.astype(jnp.float32)),
                    axis=(2, 3)) / 127.0
        codes = jnp.round(
            pages_arr.astype(jnp.float32)
            / jnp.maximum(s, 1e-10)[:, :, None, None]).astype(jnp.int8)
        return codes, s

    def measure(ps):
        maxp = max_len // ps
        pages = batch * maxp
        kp = jnp.moveaxis(flat_k.reshape(pages, ps, kvh, d), 2, 1)
        vp = jnp.moveaxis(flat_v.reshape(pages, ps, kvh, d), 2, 1)
        bt = jnp.asarray(np.arange(pages).reshape(batch, maxp), jnp.int32)
        if kv_quant:
            kp, ks = _quantize(kp)
            vp, vs = _quantize(vp)
            f = jax.jit(lambda q_, k_, v_: ragged_paged_attention(
                q_, k_, v_, bt, lengths, k_scales=ks, v_scales=vs,
                interpret=False))
        else:
            f = jax.jit(lambda q_, k_, v_: ragged_paged_attention(
                q_, k_, v_, bt, lengths, interpret=False))
        return _best_of_3(f, q, kp, vp)

    return measure


def paged_page_size(batch, num_heads, kv_heads, head_dim, max_len, dtype,
                    default: int = PAGED_DEFAULT_PAGE,
                    measure: Optional[Callable] = None,
                    cache: Optional[AutotuneCache] = None,
                    kv_quant: bool = False) -> int:
    """Tuned KV page size for a paged serving shape; measures the decode
    kernel once per shape key and caches (memory + disk), same policy
    as flash_blocks/ce_chunk (:func:`_tuned`). Used by the serving
    engine when constructed with ``page_size=None``.

    ``kv_quant`` selects the int8-page arm: its own ``:kvq`` key suffix
    (the trade-off differs — int8 pages carry a 32-row sublane tile and
    a per-page scale — so quantized and full-precision tunings never
    collide) and quantized measurement operands. Cold shapes that
    cannot measure (off-TPU, cached-only mode, under a trace) warm-start
    from the nearest tuned neighbor in the same key family instead of
    the hardcoded default."""
    cands = paged_candidates(dtype, max_len, kv_quant=kv_quant)
    key = (f"paged:{jax.default_backend()}:{jnp.dtype(dtype).name}:"
           f"b{batch}h{num_heads}kv{kv_heads}d{head_dim}:m{max_len}"
           + (":kvq" if kv_quant else ""))
    return int(_tuned(
        key, "page_size", default if default in cands else cands[0],
        lambda: cands, measure,
        lambda: _paged_measurer(batch, num_heads, kv_heads, head_dim,
                                max_len, dtype, kv_quant=kv_quant),
        cache, warm_start=True))


# --------------------------------------------------------------------------
# segment-masked (sequence-packed) flash block tuning: same cache/policy
# machinery as flash_blocks under its own "varlen" key space — the
# segment kernel's block trade-off differs from the dense kernel's (the
# skip predicate's hit rate depends on block size vs document length),
# so the two knobs tune independently.
# --------------------------------------------------------------------------

def varlen_candidates(b, bh, sq, sk, d, dtype):
    """Legal (block_q, block_k) candidates for the segment kernels,
    default first: flash legality plus the segment-array specs (k-side
    lane rule)."""
    from .tiling import flash_specs_legal, segment_specs_legal

    return [c for c in _dividing(VARLEN_CANDIDATES, sq, sk)
            if flash_specs_legal(bh, sq, sk, d, *c, dtype)
            and segment_specs_legal(b, sq, sk, *c)] \
        or [_default_blocks(sq, sk)]


def _varlen_measurer(b, sq, sk, h, kvh, d, dtype, causal):
    """Per-sweep closure for the segment kernel: operands (including a
    deterministic mixed-length packed segment layout — roughly
    doc ~ S/4, the regime the packed bench runs) materialise once."""
    from .flash_attention import flash_attention_segments

    rng = np.random.default_rng(0)
    q = _rand(rng, (b, sq, h, d), dtype)
    k = _rand(rng, (b, sk, kvh, d), dtype)
    v = _rand(rng, (b, sk, kvh, d), dtype)

    def layout(s):
        seg = np.full((b, s), -1, np.int32)
        pos = np.zeros((b, s), np.int32)
        for r in range(b):
            o = i = 0
            while o < s:
                ln = min(int(rng.integers(s // 8, s // 2)), s - o)
                seg[r, o:o + ln] = i
                pos[r, o:o + ln] = np.arange(ln)
                o += ln
                i += 1
        return jnp.asarray(seg), jnp.asarray(pos)

    seg_q, pos_q = layout(sq)
    seg_k, pos_k = (seg_q, pos_q) if sk == sq else layout(sk)

    def measure(bq, bk, interpret=False):
        def loss(q, k, v):
            return jnp.sum(flash_attention_segments(
                q, k, v, seg_q, seg_k, pos_q, pos_k, causal=causal,
                block_q=bq, block_k=bk,
                interpret=interpret).astype(jnp.float32))

        return _best_of_3(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                          q, k, v)

    return measure


def varlen_blocks(q_shape, k_shape, dtype, causal,
                  measure: Optional[Callable] = None,
                  cache: Optional[AutotuneCache] = None):
    """Tuned (block_q, block_k) for a segment-masked flash call;
    measures once per shape key and caches (memory + disk), same policy
    as flash_blocks (:func:`_tuned`). The key rides its own ``varlen:``
    prefix so dense and packed tunings never collide."""
    b, sq, h, d = q_shape
    sk, kvh = k_shape[1], k_shape[2]
    key = (f"varlen:{jax.default_backend()}:{jnp.dtype(dtype).name}:"
           f"b{b}h{h}kv{kvh}:q{sq}k{sk}d{d}:c{int(bool(causal))}")
    return _tuned(
        key, "blocks", _default_blocks(sq, sk),
        lambda: varlen_candidates(b, b * h, sq, sk, d, dtype), measure,
        lambda: _varlen_measurer(b, sq, sk, h, kvh, d, dtype, causal),
        cache, label=_blocks_label)


def flash_blocks(q_shape, k_shape, dtype, causal,
                 measure: Optional[Callable] = None,
                 cache: Optional[AutotuneCache] = None):
    """(block_q, block_k) for a dense flash call: a cache hit, else a
    measured sweep where one can run (once per shape key, cached in memory
    and on disk), else what the shape allows (tiling.flash_blocks_for,
    source ``shape-rule``: autotune off, "cached" mode, under a trace,
    not a TPU). ``measure``/``cache`` are injectable for tests."""
    from .tiling import flash_blocks_for

    b, sq, h, d = q_shape
    sk, kvh = k_shape[1], k_shape[2]
    key = (f"flash:{jax.default_backend()}:{jnp.dtype(dtype).name}:"
           f"b{b}h{h}kv{kvh}:q{sq}k{sk}d{d}:c{int(bool(causal))}")
    return _tuned(
        key, "blocks", flash_blocks_for(sq, sk, d, dtype),
        lambda: flash_candidates(b * h, sq, sk, d, dtype), measure,
        lambda: _flash_measurer(b, sq, sk, h, kvh, d, dtype, causal),
        cache, label=_blocks_label, default_source="shape-rule")
