"""Attention functionals.

Reference surface: python/paddle/nn/functional/flash_attention.py
(flash_attention:147, scaled_dot_product_attention:722). TPU-native design:
one pure attention function with a kernel-dispatch seam — the default is the
XLA softmax-attention (fused well by XLA for moderate seq lens); the Pallas
flash kernel (paddle_tpu/kernels/flash_attention.py) overrides when
available/profitable, mirroring the reference's KernelFactory choice of
flash-attn vs math path.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ...ops._op import op_fn

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "sdpa_reference", "sdpa_raw", "segment_attention_raw",
           "apply_rotary_emb",
           "fused_rotary_position_embedding", "flash_attn_unpadded",
           "segment_ids_from_cu_seqlens", "flash_attn_qkvpacked",
           "flash_attn_varlen_qkvpacked", "flash_attention_with_sparse_mask"]

# Filled by paddle_tpu.kernels at import time with a pallas implementation;
# signature (q, k, v, bias, causal, scale) -> out. None = use XLA path.
_FLASH_IMPL = None

# Segment-masked (sequence-packed) attention dispatcher, installed by
# paddle_tpu.kernels.register alongside the flash impl; signature
# (q, k, v, seg_q, seg_k, pos_q, pos_k, *, causal, scale) -> out.
# None = the pure-jnp reference (identical masking semantics).
_SEGMENT_IMPL = None


def register_flash_impl(fn):
    global _FLASH_IMPL
    _FLASH_IMPL = fn


def register_segment_impl(fn):
    global _SEGMENT_IMPL
    _SEGMENT_IMPL = fn


def segment_attention_raw(q, k, v, seg_q, seg_k, pos_q, pos_k, *,
                          causal=False, scale=None):
    """Raw-array segment-masked attention (kernel seam): the registered
    dispatcher (paddle_tpu.kernels.dispatched_segment_attention — Pallas
    segment kernel on TPU, grouped-GQA jnp reference elsewhere) when
    installed, else the reference directly. Used by sdpa_raw's packed
    path and the varlen functional surface below."""
    if _SEGMENT_IMPL is not None:
        return _SEGMENT_IMPL(q, k, v, seg_q, seg_k, pos_q, pos_k,
                             causal=causal, scale=scale)
    from ...kernels.flash_attention import segment_attention_ref
    return segment_attention_ref(q, k, v, seg_q, seg_k, pos_q, pos_k,
                                 causal=causal, scale=scale)


def sdpa_reference(q, k, v, attn_mask=None, *, causal=False, scale=None,
                   dropout_p=0.0, key=None):
    """Math attention on [B, S, H, D] (paddle layout). float32 softmax."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # [B,S,H,D] -> [B,H,S,D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    # GQA: broadcast kv heads if fewer than q heads
    if kt.shape[1] != qt.shape[1]:
        rep = qt.shape[1] // kt.shape[1]
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -jnp.inf)
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def sdpa_raw(query, key, value, attn_mask=None, *, dropout_p: float = 0.0,
             is_causal: bool = False, rng_key=None, scale=None,
             segment_ids=None, positions=None):
    """Raw-array attention dispatcher (kernel seam): flash kernel when
    registered and applicable, else the XLA math path. Used by both the
    eager op below and the functional model cores (models/llama.py).

    ``segment_ids`` [B, S] selects the sequence-packed path: tokens
    attend only within their own document (-1 = padding -> zero rows),
    with ``is_causal`` evaluated on the segment-local ``positions``
    [B, S] (defaults to the global arange, which equals the
    segment-local order for contiguously packed rows)."""
    if segment_ids is not None:
        if attn_mask is not None or dropout_p != 0.0:
            raise NotImplementedError(
                "sdpa_raw: attn_mask/dropout are not supported together "
                "with segment_ids (the packed mask IS the mask)")
        pos = positions
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(query.shape[1]),
                                   segment_ids.shape)
        return segment_attention_raw(query, key, value, segment_ids,
                                     segment_ids, pos, pos,
                                     causal=is_causal, scale=scale)
    use_flash = (_FLASH_IMPL is not None and attn_mask is None
                 and dropout_p == 0.0)
    if use_flash:
        return _FLASH_IMPL(query, key, value, causal=is_causal, scale=scale)
    return sdpa_reference(query, key, value, attn_mask, causal=is_causal,
                          scale=scale, dropout_p=dropout_p, key=rng_key)


@op_fn
def _sdpa_op(query, key, value, attn_mask=None, *, dropout_p: float = 0.0,
             is_causal: bool = False, rng_key=None, scale=None):
    return sdpa_raw(query, key, value, attn_mask, dropout_p=dropout_p,
                    is_causal=is_causal, rng_key=rng_key, scale=scale)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p: float = 0.0,
                                 is_causal: bool = False,
                                 training: bool = True, name=None,
                                 scale=None):
    """paddle scaled_dot_product_attention parity: inputs [B, S, H, D].
    Attention dropout draws its key from the framework RNG (same discipline
    as F.dropout)."""
    del name
    from ...framework import random as frandom
    p = dropout_p if training else 0.0
    rng_key = frandom.next_key() if p > 0.0 else None
    return _sdpa_op(query, key, value, attn_mask, dropout_p=p,
                    is_causal=is_causal, rng_key=rng_key, scale=scale)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle flash_attention parity (flash_attention.py:147):
    returns (out, softmax_lse-or-None)."""
    del fixed_seed_offset, rng_name, name
    out = scaled_dot_product_attention(
        query, key, value, None, dropout_p=dropout if training else 0.0,
        is_causal=causal, training=training)
    return out, None


# -- rotary position embedding (shared raw-array helpers) -------------------
# Single source of the rope math for the eager op, the incubate wrapper, and
# the functional model cores (models/llama.py). Reference surface:
# incubate/nn/functional/fused_rotary_position_embedding.py.

def rope_tables(seq_len: int, head_dim: int, *, theta: float = 10000.0,
                dtype=jnp.float32):
    """cos/sin tables [S, head_dim//2]."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))
    freqs = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction (DeepSeek-V2/V3's ``yarn_get_mscale``):
    ``0.1 * mscale * ln(factor) + 1``, or 1 where the context is not
    stretched."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, *, theta: float, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32, beta_slow: float = 1):
    """YaRN's rotary frequencies [dim // 2] as DeepSeek-V3 makes them
    (``DeepseekV3YarnRotaryEmbedding``): each pair's frequency mixed
    between the plain one (extrapolated) and the plain one over
    ``factor`` (interpolated) by a linear ramp over the pairs, from the
    pair that turns ``beta_fast`` times in the original context (all
    plain below it) to the one that turns ``beta_slow`` times (all
    interpolated above it). Float32, as the source computes it."""
    def pair(turns):
        return dim * math.log(original_max_position_embeddings
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(beta_fast)), 0)
    high = min(math.ceil(pair(beta_slow)), dim - 1)
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def gather_rope_rows(cos, sin, positions):
    """Gather per-token rope table rows at explicit positions [B, S] —
    the position_ids seam: incremental decoding gathers cache offsets,
    sequence packing gathers segment-LOCAL offsets (every document
    restarts at 0). Returns [B, S, D/2] tables rope_raw consumes."""
    return jnp.take(cos, positions, axis=0), jnp.take(sin, positions, axis=0)


def rope_raw(x, cos, sin, *, neox: bool = True):
    """Apply rope on raw arrays. x: [B, S, H, D]; cos/sin: [S, D/2] or
    (gathered at positions) [B, S, D/2]. ``neox=True`` is the rotate-half
    convention (GPT-NeoX / Llama); False the interleaved-pair convention."""
    c = cos[None, :, None, :] if cos.ndim == 2 else cos[:, :, None, :]
    s = sin[None, :, None, :] if sin.ndim == 2 else sin[:, :, None, :]
    if neox:
        d2 = x.shape[-1] // 2
        x1, x2 = x[..., :d2], x[..., d2:]
        return jnp.concatenate(
            [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)
    x1 = x[..., 0::2]
    x2 = x[..., 1::2]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


@op_fn
def apply_rotary_emb(x, cos, sin):
    """Rotary position embedding (rotate-half). x: [B, S, H, D];
    cos/sin: [S, D/2]."""
    return rope_raw(x, cos, sin)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True):
    """paddle.incubate parity wrapper: applies rope to q/k (v passed
    through). sin/cos: [1, S, 1, D] or [S, D/2] tables; ``position_ids``
    [B, S] gathers per-token table rows (incremental decoding)."""
    def table(t):
        a = t._data if hasattr(t, "_data") else jnp.asarray(t)
        if a.ndim == 4:
            a = a[0, :, 0, :]
        if a.shape[-1] == q.shape[-1]:   # full-D table -> half table
            a = a[..., : a.shape[-1] // 2]
        return a

    if cos is None or sin is None:
        cos_t, sin_t = rope_tables(q.shape[1], q.shape[-1])
    else:
        cos_t, sin_t = table(cos), table(sin)
    if position_ids is not None:
        pos = position_ids._data if hasattr(position_ids, "_data") \
            else jnp.asarray(position_ids)
        cos_t = jnp.take(cos_t, pos, axis=0)   # [B, S, D/2]
        sin_t = jnp.take(sin_t, pos, axis=0)

    outs = [_rope_op(q, cos_t, sin_t, neox=use_neox_rotary_style)]
    outs.append(_rope_op(k, cos_t, sin_t, neox=use_neox_rotary_style)
                if k is not None else None)
    outs.append(v)
    return tuple(outs)


@op_fn(name="fused_rope")
def _rope_op(x, c, s, *, neox: bool = True):
    return rope_raw(x, c, s, neox=neox)


# ---------------------------------------------------------------------------
# varlen / unpadded attention (long-context aux, SURVEY §5)
# ---------------------------------------------------------------------------

def segment_ids_from_cu_seqlens(cu_seqlens, total):
    """[0, l1, l1+l2, ...] -> per-token segment ids [total] (tokens past
    the last boundary get a padding segment of -1)."""
    import jax.numpy as jnp
    pos = jnp.arange(total)
    # segment id = number of boundaries <= pos, minus 1
    seg = jnp.searchsorted(cu_seqlens, pos, side="right") - 1
    nseg = cu_seqlens.shape[0] - 1
    return jnp.where(seg < nseg, seg, -1)


def _local_positions(cu_seqlens, seg, total):
    """Per-token offset within its own segment (padding tokens get 0)."""
    import jax.numpy as jnp
    starts = jnp.take(cu_seqlens, jnp.clip(seg, 0, None))
    return jnp.arange(total) - starts


@op_fn(name="flash_attn_varlen")
def _flash_varlen(q, k, v, seg_q, seg_k, pos_q, pos_k, *, causal, scale):
    """Packed ragged attention: q/k/v [T, H, D] with per-token segment
    ids; tokens attend only within their segment (block-diagonal mask),
    optionally causal inside each segment (on the segment-LOCAL
    positions — q and k of the same sequence can sit at different global
    offsets when cu_seqlens_q != cu_seqlens_k).

    Reference capability: nn/functional/flash_attention.py
    flash_attn_unpadded (cu_seqlens varlen kernel). TPU-native: the
    packed layout IS the TPU-friendly form (shapes stay static so jit
    never recompiles across batches of different ragged lengths), and
    the body routes through the segment-attention dispatcher — the
    Pallas segment-masked flash kernel with inter-document block
    skipping on TPU (kernels/flash_attention.py), the grouped-GQA jnp
    reference elsewhere. No [H, T, T] score matrix materialises on the
    kernel path, and GQA no longer repeats k/v."""
    out = segment_attention_raw(
        q[None], k[None], v[None], seg_q[None], seg_k[None],
        pos_q[None], pos_k[None], causal=causal, scale=scale)
    return out[0]


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q=None, max_seqlen_k=None, scale=None,
                        dropout=0.0, causal=False, return_softmax=False,
                        name=None):
    """paddle.nn.functional.flash_attn_unpadded parity: packed [T, H, D]
    tensors + cu_seqlens prefix sums -> (out, None)."""
    from ...ops._op import unwrap, wrap
    if dropout:
        raise NotImplementedError(
            "flash_attn_unpadded: attention dropout is not implemented on "
            "the varlen path (pass dropout=0.0)")
    if return_softmax:
        raise NotImplementedError(
            "flash_attn_unpadded: return_softmax=True is not supported "
            "(the packed softmax is never materialized)")
    cq, ck = unwrap(cu_seqlens_q), unwrap(cu_seqlens_k)
    tq = unwrap(query).shape[0]
    tk = unwrap(key).shape[0]
    # A prefix sum reaching PAST the packed tensor would silently
    # mis-segment every sequence after the overflow point (tokens it
    # claims don't exist); cu[-1] < T is the documented trailing-padding
    # convention and stays legal. Checked eagerly only — under a trace
    # the values are abstract and the mask math is still well-defined.
    for name, cu, t in (("cu_seqlens_q", cq, tq), ("cu_seqlens_k", ck, tk)):
        try:
            last = int(cu[-1])
        except (TypeError, jax.errors.ConcretizationTypeError):
            continue   # traced values: mask math stays well-defined
        if last > t:
            from ...core import enforce as E
            raise E.InvalidArgumentError(
                f"flash_attn_unpadded: {name}[-1] == {last} exceeds the "
                f"packed tensor length T == {t}; the prefix sums must "
                f"end at or before the token count (trailing tokens "
                f"past {name}[-1] are treated as padding)")
    seg_q = segment_ids_from_cu_seqlens(cq, tq)
    seg_k = segment_ids_from_cu_seqlens(ck, tk)
    out = _flash_varlen(query, key, value, wrap(seg_q), wrap(seg_k),
                        wrap(_local_positions(cq, seg_q, tq)),
                        wrap(_local_positions(ck, seg_k, tk)),
                        causal=bool(causal), scale=scale)
    return out, None


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, *, fixed_seed_offset=None,
                         rng_name="", training=True, name=None):
    """paddle flash_attn_qkvpacked parity (flash_attention.py:303):
    qkv [B, S, 3, H, D] -> (out, None)."""
    from ...ops._op import unwrap, wrap
    qkva = unwrap(qkv)
    q, k, v = (wrap(qkva[:, :, i]) for i in range(3))
    return flash_attention(q, k, v, dropout=dropout, causal=causal,
                           return_softmax=return_softmax, training=training)


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q=None, max_seqlen_k=None,
                                scale=None, dropout=0.0, causal=False,
                                return_softmax=False, fixed_seed_offset=None,
                                rng_name="", varlen_padded=True, name=None):
    """paddle flash_attn_varlen_qkvpacked parity (flash_attention.py:594):
    packed qkv [T, 3, H, D] + cu_seqlens -> (out, None)."""
    from ...ops._op import unwrap, wrap
    qkva = unwrap(qkv)
    q, k, v = (wrap(qkva[:, i]) for i in range(3))
    return flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k,
                               max_seqlen_q, max_seqlen_k, scale,
                               dropout=dropout, causal=causal,
                               return_softmax=return_softmax)


def flash_attention_with_sparse_mask(query, key, value,
                                     attn_mask_start_row_indices,
                                     attn_mask_start_row=0, dropout_p=0.0,
                                     is_causal=False, return_softmax=False,
                                     return_softmax_lse=False,
                                     return_seed_offset=False, training=True,
                                     name=None):
    """paddle flash_attention_with_sparse_mask parity
    (flash_attention.py:844). ``attn_mask_start_row_indices`` [B, H, S]
    gives, per key column j, the first query row that may NOT attend to it
    (rows >= start are masked). Composed with the causal mask when
    ``is_causal``; evaluated as a dense masked softmax (MXU path)."""
    from ...ops._op import unwrap, wrap
    if return_softmax or return_softmax_lse or return_seed_offset:
        raise NotImplementedError(
            "flash_attention_with_sparse_mask: softmax/lse/seed returns "
            "are not materialized on this path")
    q = unwrap(query)
    starts = unwrap(attn_mask_start_row_indices)
    sq = q.shape[1]
    rows = jnp.arange(sq)
    allowed = rows[None, None, :, None] < starts[:, :, None, :]  # [B,H,Sq,Sk]
    if is_causal:
        allowed = allowed & (rows[:, None] >= rows[None, :])[None, None]
    mask = wrap(allowed)
    out = scaled_dot_product_attention(
        query, key, value, mask,
        dropout_p=dropout_p if training else 0.0, is_causal=False,
        training=training)
    return out, None
