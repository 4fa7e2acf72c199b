"""Pallas TPU ragged paged attention (decode).

Reference capability: the vLLM-style PagedAttention decode kernel
(csrc/attention/paged_attention_v1.cu in the reference serving stacks) as
rebuilt TPU-native by Ragged Paged Attention (arxiv 2604.15464): each
sequence's KV cache lives in non-contiguous fixed-size pages named by a
block table, and one decode query attends over exactly its own ragged
length — no batch-uniform max-length padding in either HBM traffic or
FLOPs.

TPU-native design (follows flash_attention.py's canonical pattern):
- Grid ``(batch, kv_heads, max_pages)`` with the page axis sequential per
  core, carrying the online-softmax running max/denominator in VMEM
  scratch exactly like the flash forward.
- The block table and per-request lengths ride a
  ``PrefetchScalarGridSpec`` scalar prefetch: the K/V BlockSpec index
  maps read ``block_table[b, p]`` to aim the automatic HBM->VMEM DMA at
  the right page — the gather IS the BlockSpec, no in-kernel DMA code.
- Pages past a sequence's length are predicated off (``pl.when``), so a
  short sequence in a long-batch grid costs control flow only; the
  final partial page is masked per-position. A length of 0 (empty slot
  in the serving engine's fixed slot grid) produces a zero output row.
- GQA: queries reshape to [B, kv_heads, group, head_dim]; the group dim
  is zero-padded to the sublane tile so every matmul is legal.

Layouts: pages are ``[num_pages, kv_heads, page_size, head_dim]`` (the
kv-head axis OUTSIDE the page axis so a (1, 1, page, hd) block satisfies
Mosaic's last-two-dims tiling rule for any page size); q is
``[batch, num_heads, head_dim]`` — one decode position per sequence.

``paged_attention_ref`` is the pure-jnp gather fallback — identical
math, runs on every backend — which tier-1 exercises on CPU and the
dispatcher (kernels/__init__.py) uses when the kernel is unsupported.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _sublane(dtype) -> int:
    return 16 if jnp.dtype(dtype).itemsize == 2 else 8


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _page_scale(s_ref, pi):
    """This page's scale, as a [1, 1] tile, out of the sequence's
    [1, max_pages] row of per-page scales: a masked lane reduction
    (Mosaic has no dynamic lane index)."""
    row = s_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(lane == pi, row, 0.0), axis=1, keepdims=True)


def _decode_kernel(bt_ref, len_ref, *refs, scale, page_size, max_pages,
                   quant):
    if quant:
        # int8 pages ride with this (sequence, kv-head)'s row of per-page
        # scales: dequant is one multiply FOLDED into the dots — the page
        # DMA itself stays int8
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc, m_s, l_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s = refs
    b = pl.program_id(0)
    pi = pl.program_id(2)
    length = len_ref[b]
    npages = (length + page_size - 1) // page_size

    @pl.when(pi == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    # a page wholly past this sequence's length contributes nothing —
    # the ragged skip that makes mixed-length batches cheap
    @pl.when(pi < npages)
    def _body():
        q = q_ref[0, 0]                                  # [gp, hd]
        k = k_ref[0, 0]                                  # [ps, hd]
        if quant:
            # every code in this (page, head) block shares ONE scale,
            # so dot(q, codes) * (ks*scale) == dot(q, deq(codes)) * scale
            s = jax.lax.dot_general(
                q.astype(jnp.float32), k.astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) \
                * (_page_scale(ks_ref, pi) * scale)      # [gp, ps]
        else:
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
        pos = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, _NEG_INF)         # partial last page

        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_s[:] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), l_s.shape)
        if quant:
            acc[:] = acc[:] * alpha + jax.lax.dot_general(
                p, v_ref[0, 0].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) \
                * _page_scale(vs_ref, pi)
        else:
            acc[:] = acc[:] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(pi == max_pages - 1)
    def _finalize():
        l = l_s[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                  # empty slot -> 0
        o_ref[0, 0] = (acc[:] / l).astype(o_ref.dtype)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           scale=None, k_scales=None, v_scales=None,
                           interpret=False):
    """Paged decode attention. q: [B, num_heads, head_dim]; k_pages /
    v_pages: [num_pages, kv_heads, page_size, head_dim]; block_tables:
    [B, max_pages] page ids (entries past a sequence's pages may hold
    any value — they are clamped and masked); lengths: [B] valid KV
    positions per sequence (0 = empty slot -> zero output row).

    With ``k_scales``/``v_scales`` ([num_pages, kv_heads] f32, both or
    neither) the pages are int8 codes (FLAGS_serving_kv_quant): the
    block table gathers each sequence's scales into a [max_pages] row
    per kv head (a tiny XLA gather), the row is fetched once per
    (sequence, kv head), and dequantization folds into the two dots —
    HBM page traffic stays int8. Returns [B, num_heads, head_dim]."""
    quant = k_scales is not None
    B, nh, hd = q.shape
    P, kv, ps, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = nh // kv
    sub = _sublane(q.dtype)
    gp = max(sub, (g + sub - 1) // sub * sub)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    qg = q.reshape(B, kv, g, hd)
    if gp != g:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    # clamp: padded/garbage table entries must still name a real page for
    # the BlockSpec DMA; their contribution is masked by ``lengths``
    bt = jnp.clip(block_tables, 0, P - 1).reshape(-1).astype(jnp.int32)

    def _page_map(b, h, p, bt_, ln_, mp=maxp):
        return (bt_[b * mp + p], h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, gp, hd),
                     lambda b, h, p, bt_, ln_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, ps, hd), _page_map),
        pl.BlockSpec((1, 1, ps, hd), _page_map),
    ]
    operands = [qg, k_pages, v_pages]
    if quant:
        # Mosaic refuses a (1, 1) block of the [P, kv] scale plane (the
        # last two block dims must divide (8, 128) or equal the array's),
        # so the plane is gathered per sequence here: [B, kv, 1, maxp],
        # whose (1, 1, 1, maxp) block is legal by the "equal" arm
        def rows(scales):
            per_seq = scales.astype(jnp.float32)[bt.reshape(B, maxp)]
            return jnp.swapaxes(per_seq, 1, 2)[:, :, None, :]

        row_spec = pl.BlockSpec((1, 1, 1, maxp),
                                lambda b, h, p, bt_, ln_: (b, h, 0, 0))
        in_specs += [row_spec, row_spec]
        operands += [rows(k_scales), rows(v_scales)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, kv, maxp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, gp, hd),
                               lambda b, h, p, bt_, ln_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, hd), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, page_size=ps,
                          max_pages=maxp, quant=quant),
        name="paged_decode_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kv, gp, hd), q.dtype),
        interpret=interpret,
    )(bt, lengths.astype(jnp.int32), *operands)
    return out[:, :, :g, :].reshape(B, nh, hd)


# ---------------------------------------------------------------------------
# pure-jnp fallback (identical math; every backend)
# ---------------------------------------------------------------------------

def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        scale=None, k_scales=None, v_scales=None):
    """Gather-based reference: same contract and masking semantics as the
    kernel (safe softmax — an empty sequence yields a zero row, never
    NaN). This is the path tier-1 runs on CPU. ``k_scales``/``v_scales``
    ([num_pages, kv_heads] f32) mark int8 pages: the gathered codes are
    dequantized in f32 before the same einsum math."""
    B, nh, hd = q.shape
    P, kv, ps, _ = k_pages.shape
    maxp = block_tables.shape[1]
    g = nh // kv
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bt = jnp.clip(block_tables, 0, P - 1).reshape(-1)
    # flat gathers with in-bounds promise (clip above), consumed in page
    # layout directly — XLA:CPU's generic gather/transpose lowering is
    # this fallback's hot spot, so no moveaxis copies
    k = k_pages.at[bt].get(
        mode="promise_in_bounds").reshape(B, maxp, kv, ps, hd)
    v = v_pages.at[bt].get(
        mode="promise_in_bounds").reshape(B, maxp, kv, ps, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scales is not None:
        sk = k_scales.at[bt].get(
            mode="promise_in_bounds").reshape(B, maxp, kv)
        sv = v_scales.at[bt].get(
            mode="promise_in_bounds").reshape(B, maxp, kv)
        kf = kf * sk.astype(jnp.float32)[..., None, None]
        vf = vf * sv.astype(jnp.float32)[..., None, None]
    qf = q.astype(jnp.float32).reshape(B, kv, g, hd)
    s = jnp.einsum("bkgd,bmkpd->bkgmp", qf, kf) * scale
    pos = jnp.arange(maxp)[:, None] * ps + jnp.arange(ps)[None, :]
    mask = pos[None] < lengths[:, None, None]          # [B, maxp, ps]
    s = jnp.where(mask[:, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=(-2, -1), keepdims=True)
    e = jnp.where(mask[:, None, None], jnp.exp(s - m), 0.0)
    l = jnp.sum(e, axis=(-2, -1), keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bkgmp,bmkpd->bkgd", e / l, vf)
    return out.reshape(B, nh, hd).astype(q.dtype)


def supported(q, k_pages, block_tables, quant=False) -> bool:
    """Whether the pallas kernel handles these shapes (else the
    dispatcher uses paged_attention_ref). ``quant`` marks the int8-page
    arm (scale planes present)."""
    if q.ndim != 3 or k_pages.ndim != 4 or block_tables.ndim != 2:
        return False
    B, nh, hd = q.shape
    P, kv, ps, hd2 = k_pages.shape
    if hd != hd2 or hd > 256 or nh % kv != 0:
        return False
    if jnp.dtype(q.dtype) not in (jnp.dtype(jnp.float32),
                                  jnp.dtype(jnp.bfloat16)):
        return False
    if quant:
        # int8 pages: the K/V block's sublane tile is 32 rows (1-byte
        # dtype), and only int8 codes are a valid quantized pool
        if jnp.dtype(k_pages.dtype) != jnp.dtype(jnp.int8) or ps % 32:
            return False
    elif jnp.dtype(k_pages.dtype) == jnp.dtype(jnp.int8):
        return False     # int8 pool without scales is a contract breach
    # page rows must cover the dtype's sublane tile (16 for bf16) and
    # the lane dim should fill VREGs; anything smaller falls back
    return hd % 8 == 0 and ps % _sublane(q.dtype) == 0 and P >= 1
