"""Pallas TPU flash attention (forward + backward).

Reference capability: paddle/phi/kernels/gpu/flash_attn_kernel.cu (wrapping
third_party/flashattn) and nn/functional/flash_attention.py. TPU-native
design: tiled online-softmax kernels on the MXU whose time is set by
their products, not by their step count. A grid step of the forward and
dq kernels owns one q block and holds a *span* of K and V resident in
VMEM — the whole sequence where it fits, and then it is fetched once a
kv head, because its block index does not change with the q block — and
walks the span's ``block_k`` sub-blocks in a loop that ends at the
diagonal: first the sub-blocks every row of the q block sees whole (no
mask is built for them), then the ones the diagonal crosses. Nothing is
run, and nothing fetched, for a sub-block wholly above the diagonal. The
dkv kernel is the same loop turned round: a grid step owns one k block,
holds a span of q and dO, starts at the diagonal and computes its tiles
transposed ([block_k, block_q]) so that every product is one the MXU
does without a transpose. GQA queries map to their kv head via the
BlockSpec index map, the backward pass recomputes probabilities from the
saved log-sum-exp (no S×S materialisation anywhere), and the softmax
scale is folded into q once, in the copy that lays it out for the
kernels.

Layouts: public API is paddle's [B, S, H, D]; kernels run on [B*H, S, D].
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tiling import flash_blocks_for, flash_span, flash_specs_legal

# the segment kernels' blocks (the dense kernels take theirs from the
# shape: tiling.flash_blocks_for)
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))     # a @ b.T, the MXU's own form


def _cols(x, n):
    """A lane-replicated [rows, 128] statistic as [rows, n]: whole lane
    tiles are repeated, which costs no broadcast."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _sub(i, block, count):
    """Rows (or lanes) of sub-block ``i`` of a resident span."""
    if count == 1:
        return pl.ds(0, block)
    return pl.ds(pl.multiple_of(i * block, block), block)


def k_loop_bounds(qi, span0, *, offset, block_q, block_k, count):
    """Which ``block_k`` sub-blocks of a K/V span that starts at column
    ``span0`` the q block ``qi`` visits: ``[0, whole)`` are seen whole by
    every row, ``[whole, end)`` are crossed by the diagonal, the rest is
    hidden. Bottom-right-aligned causal (sdpa convention): row r sees
    columns <= r + offset, offset = sk - sq."""
    first = qi * block_q + offset - span0 + 1   # columns the first row sees
    last = first + block_q - 1                  # ... and the last row
    whole = jnp.minimum(jnp.maximum(first, 0) // block_k, count)
    end = jnp.minimum(pl.cdiv(jnp.maximum(last, 0), block_k), count)
    return whole, end


def k_window_bounds(qi, span0, *, window, offset, block_q, block_k, count):
    """Where a window of ``window`` keys (a row sees columns > r + offset
    - window) cuts the same span: sub-blocks before ``start`` lie wholly
    behind every row's window, ``[start, clear)`` are crossed by its
    edge, and from ``clear`` on it hides nothing."""
    first = qi * block_q + offset - window + 1 - span0   # first row's edge
    last = first + block_q - 1                           # the last row's
    start = jnp.minimum(jnp.maximum(first, 0) // block_k, count)
    clear = jnp.minimum(pl.cdiv(jnp.maximum(last, 0), block_k), count)
    return start, clear


def q_loop_bounds(ki, span0, *, offset, block_q, block_k, count):
    """The dkv kernel's turn of :func:`k_loop_bounds`: of a q span that
    starts at row ``span0``, sub-blocks ``[start, whole)`` are crossed by
    the diagonal of k block ``ki`` and ``[whole, count)`` see it whole;
    the ones before ``start`` see none of it."""
    hidden = ki * block_k - offset - span0      # rows that see no column
    partly = hidden + block_k - 1               # ... or not every column
    start = jnp.minimum(jnp.maximum(hidden, 0) // block_q, count)
    whole = jnp.minimum(pl.cdiv(jnp.maximum(partly, 0), block_q), count)
    return start, whole


def _row_minus_col(shape, q_axis):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))


def _loop(lo, hi, step):
    """``step(i)`` for i in [lo, hi); the state lives in scratch refs."""
    def body(i, carry):
        step(i)
        return carry
    jax.lax.fori_loop(lo, hi, body, 0)


def _walk_k(step, qi, span0, *, causal, count, window=None, **geometry):
    """``step(c, crossed)`` for every sub-block of a K/V span that q block
    ``qi`` sees, the ones it sees whole first. With a ``window`` (causal
    only) the walk starts at the window's first sub-block: the ones its
    edge crosses, the ones seen whole, the ones the diagonal crosses (a
    sub-block both cross is walked once, masked)."""
    if causal and window is not None:
        whole, end = k_loop_bounds(qi, span0, count=count, **geometry)
        start, clear = k_window_bounds(qi, span0, window=window,
                                       count=count, **geometry)
        edge = jnp.minimum(clear, end)
        diag = jnp.maximum(whole, edge)
        _loop(start, edge, lambda c: step(c, True))
        _loop(edge, diag, lambda c: step(c, False))
        _loop(diag, end, lambda c: step(c, True))
    elif causal:
        whole, end = k_loop_bounds(qi, span0, count=count, **geometry)
        _loop(0, whole, lambda c: step(c, False))
        _loop(whole, end, lambda c: step(c, True))
    else:
        _loop(0, count, lambda c: step(c, False))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                causal, offset, block_k, window=None):
    block_q, d = q_ref.shape[1:]
    span = k_ref.shape[1]
    count = span // block_k
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    q = q_ref[0]                                        # [bq, d], scaled
    diag0 = kj * span - qi * block_q - offset
    if causal:
        row_col = _row_minus_col((block_q, block_k), 0)

    def step(c, crossed):
        rows = _sub(c, block_k, count)
        s = jax.lax.dot_general(q, k_ref[0, rows, :], _NT,
                                preferred_element_type=jnp.float32)
        if crossed:
            seen = row_col >= diag0 + c * block_k
            if window is not None:
                # (a row with no key in its first sub-block adds ones to
                # its sums there; the first key it does see, and the
                # diagonal is always one, scales them by exp(-1e30) = 0)
                seen = jnp.logical_and(
                    seen, row_col < diag0 + c * block_k + window)
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_s[:]                                 # [bq, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _cols(m_new, block_k))          # [bq, bk]
        l_s[:] = l_s[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * _cols(alpha, d) + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, rows, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[:] = m_new

    _walk_k(step, qi, kj * span, causal=causal, offset=offset,
            block_q=block_q, block_k=block_k, count=count, window=window)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        l = l_s[:]
        l = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows
        o_ref[0] = (acc[:] / _cols(l, d)).astype(o_ref.dtype)
        lse_ref[0] = (m_s[:] + jnp.log(l))[:, :1]       # [bq, 1]


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _q_major(q, k, *, causal, block_q, block_k, window=None):
    """Grid and BlockSpecs of the forward and dq kernels: (grid, a q
    block's rows, its row statistics, a K or V span). A span wholly above
    a q block's diagonal keeps the index of the last one that is not, so
    that it is not fetched; with a ``window``, one wholly behind it the
    index of the first that is not."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    span = flash_span(sk, block_k, d, k.dtype)
    spans = sk // span
    if causal and spans > 1:
        def at(i, j):
            last = ((i + 1) * block_q - 1 + sk - sq) // span
            j = jnp.minimum(j, jnp.clip(last, 0, spans - 1))
            if window is None:
                return j
            first = (i * block_q + sk - sq - window + 1) // span
            return jnp.maximum(j, jnp.clip(first, 0, spans - 1))
    else:
        def at(i, j):
            return j
    row = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    # LSE rides a trailing singleton lane dim: Mosaic requires the last
    # two block dims be (8, 128)-divisible OR equal to the array dims —
    # (block_q, 1) over [bh, sq, 1] satisfies the "equal" arm (a bare
    # (1, block_q) block over [bh, sq] is illegal and killed BENCH_r02).
    stat = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    kv = pl.BlockSpec((1, span, d),
                      lambda b, i, j: (b // group, at(i, j), 0))
    return (bh, sq // block_q, spans), row, stat, kv


def _fwd(q, k, v, *, causal, block_q, block_k, interpret, window=None):
    """q: [BH, Sq, D], already scaled; k/v: [BKV, Sk, D] with
    BH = BKV * group."""
    bh, sq, d = q.shape
    grid, row, stat, kv = _q_major(q, k, causal=causal, block_q=block_q,
                                   block_k=block_k, window=window)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal,
                          offset=k.shape[1] - sq, block_k=block_k,
                          window=window),
        name="flash_fwd",
        grid=grid,
        in_specs=[row, kv, kv],
        out_specs=[row, stat],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, offset, block_k):
    block_q = q_ref.shape[1]
    span = k_ref.shape[1]
    count = span // block_k
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0]
    do = do_ref[0]
    # the row statistics, lane-replicated once a q block
    lse = jnp.broadcast_to(lse_ref[0], (block_q, _LANES))
    delta = jnp.broadcast_to(delta_ref[0], (block_q, _LANES))
    diag0 = kj * span - qi * block_q - offset
    if causal:
        row_col = _row_minus_col((block_q, block_k), 0)

    def step(c, crossed):
        rows = _sub(c, block_k, count)
        kk = k_ref[0, rows, :]
        s = jax.lax.dot_general(q, kk, _NT,
                                preferred_element_type=jnp.float32)
        if crossed:
            s = jnp.where(row_col >= diag0 + c * block_k, s, _NEG_INF)
        p = jnp.exp(s - _cols(lse, block_k))
        dp = jax.lax.dot_general(do, v_ref[0, rows, :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - _cols(delta, block_k))
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _walk_k(step, qi, kj * span, causal=causal, offset=offset,
            block_q=block_q, block_k=block_k, count=count)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finalize():
        # q came scaled, so ds is the gradient of the scaled scores
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, causal, offset,
                    block_q):
    block_k = k_ref.shape[1]
    span = q_ref.shape[1]
    count = span // block_q
    ki = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    kk = k_ref[0]
    v = v_ref[0]
    diag0 = ki * block_k - qj * span - offset
    if causal:
        row_col = _row_minus_col((block_k, block_q), 1)

    def step(i, crossed):
        rows = _sub(i, block_q, count)
        q = q_ref[0, rows, :]                           # scaled
        do = do_ref[0, rows, :]
        # every tile transposed, [bk, bq]: the row statistics lie along
        # the lanes and broadcast over sublanes
        s = jax.lax.dot_general(kk, q, _NT,
                                preferred_element_type=jnp.float32)
        if crossed:
            s = jnp.where(row_col >= diag0 - i * block_q, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, rows])
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, d]
        dp = jax.lax.dot_general(v, do, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, :, rows])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, d]

    if causal:
        start, whole = q_loop_bounds(ki, qj * span, offset=offset,
                                     block_q=block_q, block_k=block_k,
                                     count=count)
        _loop(start, whole, lambda i: step(i, True))
        _loop(whole, count, lambda i: step(i, False))
    else:
        _loop(0, count, lambda i: step(i, False))

    @pl.when(qj == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(res, g, *, scale, causal, block_q, block_k, interpret):
    q, k, v, out, lse = res
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    offset = sk - sq
    do = g.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)              # [BH, Sq, 1]

    grid, row, stat, kv = _q_major(q, k, causal=causal, block_q=block_q,
                                   block_k=block_k)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          offset=offset, block_k=block_k),
        name="flash_bwd_dq",
        grid=grid,
        in_specs=[row, kv, kv, row, stat, stat],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv computed per *query* head then group-summed to the kv head
    # (avoids cross-program races for GQA). A q span wholly above a k
    # block's diagonal keeps the index of the first one that is not, so
    # that it is not fetched.
    span = flash_span(sq, block_q, d, q.dtype)
    spans = sq // span
    if causal and spans > 1:
        def at(j, i):
            return jnp.maximum(
                i, jnp.clip((j * block_k - offset) // span, 0, spans - 1))
    else:
        def at(j, i):
            return i
    rows = pl.BlockSpec((1, span, d), lambda b, j, i: (b, at(j, i), 0))
    # the statistics lane-major, [BH, 1, Sq]: a transposed tile wants
    # them along its lanes
    stats = pl.BlockSpec((1, 1, span), lambda b, j, i: (b, 0, at(j, i)))
    col_in = pl.BlockSpec((1, block_k, d),
                          lambda b, j, i: (b // group, j, 0))
    col_out = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    dk_full, dv_full = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, offset=offset,
                          block_q=block_q),
        name="flash_bwd_dkv",
        grid=(bh, sk // block_k, spans),
        in_specs=[rows, col_in, col_in, rows, stats, stats],
        out_specs=[col_out, col_out],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v, do, lse.reshape(bh, 1, sq), delta.reshape(bh, 1, sq))

    if group > 1:
        dk = dk_full.reshape(bkv, group, sk, d).sum(axis=1)
        dv = dv_full.reshape(bkv, group, sk, d).sum(axis=1)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# public entry (custom_vjp over [B, S, H, D])
# ---------------------------------------------------------------------------

def _reshape_in(x):
    """[B, S, H, D] -> [B*H, S, D]."""
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _reshape_out(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    # the scale goes into q once, in the copy that lays it out: the
    # kernels' score tiles come out scaled, and dk with them
    qr = _reshape_in(q * scale)
    kr = _reshape_in(k)
    vr = _reshape_in(v)
    out, lse = _fwd(qr, kr, vr, causal=causal, block_q=block_q,
                    block_k=block_k, interpret=interpret)
    return _reshape_out(out, b, h), (qr, kr, vr, out, lse, b, h)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    qr, kr, vr, out, lse, b, h = res
    kvh = kr.shape[0] // b
    gr = _reshape_in(g)
    dq, dk, dv = _bwd((qr, kr, vr, out, lse), gr, scale=scale,
                      causal=causal, block_q=block_q, block_k=block_k,
                      interpret=interpret)
    return (_reshape_out(dq, b, h), _reshape_out(dk, b, kvh),
            _reshape_out(dv, b, kvh))


_flash.defvjp(lambda q, k, v, *a: _flash_fwd(q, k, v, *a),
              _flash_bwd)


def _blocks(q, k, block_q, block_k):
    """The blocks a call runs: the caller's, clamped to the sequence, or
    the shape rule's where it names none."""
    rule = flash_blocks_for(q.shape[1], k.shape[1], q.shape[-1], q.dtype)
    return (min(block_q or rule[0], q.shape[1]),
            min(block_k or rule[1], k.shape[1]))


def flash_attention(q, k, v, *, causal=False, scale=None,
                    block_q=None, block_k=None, window=None,
                    interpret=False):
    """Flash attention on [B, S, H, D] (paddle layout); supports GQA
    (fewer kv heads) and causal masking. Differentiable (custom VJP,
    flash backward). ``block_q`` / ``block_k`` default to what the shape
    allows (``tiling.flash_blocks_for``). Sequence lengths must divide
    the block sizes — the dispatcher (kernels/__init__.py) falls back to
    the XLA path otherwise. ``window`` (causal only, FORWARD only: no
    backward pass is written for it) lets a query see its own position
    and the ``window - 1`` before it: the K loop starts at the window's
    first sub-block and the spans behind it are never fetched."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    bq, bk = _blocks(q, k, block_q, block_k)
    if window is not None:
        if not causal:
            raise ValueError("a window is a causal mask's lower bound")
        b, _, h, _ = q.shape
        out, _ = _fwd(_reshape_in(q * float(scale)), _reshape_in(k),
                      _reshape_in(v), causal=True, block_q=bq, block_k=bk,
                      interpret=interpret, window=int(window))
        return _reshape_out(out, b, h)
    return _flash(q, k, v, float(scale), bool(causal), bq, bk, interpret)


def supported(q, k, v, *, block_q=None, block_k=None):
    """Whether the kernel handles these shapes (else XLA fallback).

    Beyond divisibility, this checks Mosaic's block-shape legality for
    every BlockSpec the kernels will emit (tiling.block_legal) — interpret
    mode can't catch an illegal block, so the dispatcher must reject it
    here before a doomed pallas_call is traced (BENCH_r02's failure mode).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq, bk = _blocks(q, k, block_q, block_k)
    return (sq % bq == 0 and sk % bk == 0 and
            bq % 8 == 0 and bk % 8 == 0 and
            h % k.shape[2] == 0 and d <= 256 and
            flash_specs_legal(b * h, sq, sk, d, bq, bk, q.dtype))


# ---------------------------------------------------------------------------
# segment-aware (sequence-packed) flash attention
#
# Packed training rows hold several documents back to back, tagged by a
# per-token segment id (-1 = padding). The kernels below fuse the
# same-segment mask (and the segment-LOCAL causal mask) into the
# online-softmax tiles, and prefetch per-block min/max segment ids /
# positions (splash-attention style, PrefetchScalarGridSpec) so a block
# pair that cannot contain any same-segment (and, when causal, any
# non-future) token pair skips its matmuls entirely — packing becomes a
# FLOPs win on top of the padding win.
# ---------------------------------------------------------------------------

# rows of the prefetched per-block stats array (int32, [6, B * stride]):
_ST_QSMIN, _ST_QSMAX, _ST_KSMIN, _ST_KSMAX, _ST_QPMAX, _ST_KPMIN = range(6)


def _seg_block_stats(seg_q, seg_k, pos_q, pos_k, block_q, block_k):
    """Per-block segment/position extrema for the skip predicate.
    seg/pos: [B, S] int32 (already block-divisible). Returns
    (stats [6, B*stride] int32, stride) with q blocks at
    ``b*stride + qi`` and k blocks at ``b*stride + ki``."""
    b, sq = seg_q.shape
    sk = seg_k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    stride = max(nq, nk)

    def pad(a):
        return jnp.pad(a, ((0, 0), (0, stride - a.shape[1])))

    qs = seg_q.reshape(b, nq, block_q)
    ks = seg_k.reshape(b, nk, block_k)
    qp = pos_q.reshape(b, nq, block_q)
    kp = pos_k.reshape(b, nk, block_k)
    stats = jnp.stack([
        pad(qs.min(-1)), pad(qs.max(-1)),
        pad(ks.min(-1)), pad(ks.max(-1)),
        pad(qp.max(-1)), pad(kp.min(-1)),
    ]).astype(jnp.int32).reshape(6, b * stride)
    return stats, stride


def _seg_run_predicate(stats_ref, qb, kb, causal):
    """Scalar block-skip predicate (reads prefetched SMEM stats).

    A (q-block, k-block) pair can contribute iff some pair of tokens
    shares a (non-padding) segment id — interval overlap of
    [max(min,0), max] is conservative for any layout and exact for
    contiguous packing — and, when causal, some k token's segment-local
    position does not exceed every q token's (min pos_k <= max pos_q:
    otherwise every same-segment pair is strictly future and masked)."""
    qsmax = stats_ref[_ST_QSMAX, qb]
    ksmax = stats_ref[_ST_KSMAX, kb]
    run = jnp.logical_and(
        jnp.logical_and(qsmax >= 0, ksmax >= 0),
        jnp.logical_and(
            jnp.maximum(stats_ref[_ST_QSMIN, qb], 0) <= ksmax,
            jnp.maximum(stats_ref[_ST_KSMIN, kb], 0) <= qsmax))
    if causal:
        run = jnp.logical_and(
            run, stats_ref[_ST_KPMIN, kb] <= stats_ref[_ST_QPMAX, qb])
    return run


def count_skipped_blocks(seg_q, seg_k, pos_q, pos_k, block_q, block_k,
                         causal):
    """(skipped, total) block pairs for one head's grid — the exact
    predicate the kernels run, computed eagerly for metrics/bench (every
    head sees the same segment layout, so the fraction is per-head
    invariant). Inputs [B, S]; block sizes must divide S."""
    seg_q = jnp.asarray(seg_q, jnp.int32)
    seg_k = jnp.asarray(seg_k, jnp.int32)
    pos_q = jnp.asarray(pos_q, jnp.int32)
    pos_k = jnp.asarray(pos_k, jnp.int32)
    b, sq = seg_q.shape
    nq, nk = sq // block_q, seg_k.shape[1] // block_k
    stats, stride = _seg_block_stats(seg_q, seg_k, pos_q, pos_k,
                                     block_q, block_k)
    st = stats.reshape(6, b, stride)
    qsmin, qsmax = st[_ST_QSMIN, :, :nq], st[_ST_QSMAX, :, :nq]
    ksmin, ksmax = st[_ST_KSMIN, :, :nk], st[_ST_KSMAX, :, :nk]
    run = ((qsmax[:, :, None] >= 0) & (ksmax[:, None, :] >= 0)
           & (jnp.maximum(qsmin, 0)[:, :, None] <= ksmax[:, None, :])
           & (jnp.maximum(ksmin, 0)[:, None, :] <= qsmax[:, :, None]))
    if causal:
        run = run & (st[_ST_KPMIN, :, None, :nk]
                     <= st[_ST_QPMAX, :, :nq, None])
    total = b * nq * nk
    return total - int(jnp.sum(run)), total


def _seg_mask(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal):
    """[bq, bk] same-segment (and causal) mask from the per-token refs:
    q side rides [bq, 1] blocks, k side [1, bk] — the compare broadcasts
    straight to the score tile shape."""
    same = jnp.logical_and(qseg_ref[0] == kseg_ref[0], qseg_ref[0] >= 0)
    if causal:
        same = jnp.logical_and(same, qpos_ref[0] >= kpos_ref[0])
    return same


def _seg_fwd_kernel(stats_ref, q_ref, k_ref, v_ref, qseg_ref, kseg_ref,
                    qpos_ref, kpos_ref, o_ref, lse_ref, acc, m_s, l_s, *,
                    scale, causal, nh, stride, num_k_blocks):
    b = pl.program_id(0) // nh
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    run = _seg_run_predicate(stats_ref, b * stride + qi, b * stride + ki,
                             causal)

    @pl.when(run)
    def _body():
        q = q_ref[0]                                    # [bq, d]
        k = k_ref[0]                                    # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        same = _seg_mask(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal)
        s = jnp.where(same, s, _NEG_INF)

        m_prev = m_s[:, :1]                             # [bq, 1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # p masked (not just s): a fully-masked ROW has m_new = -1e30,
        # where exp(s - m_new) would be 1 per lane and corrupt l — the
        # mask keeps padding rows at l == 0 so finalize emits exact 0s
        p = jnp.where(same, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        l = l_s[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                 # padding rows -> 0
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_s[:, :1] + jnp.log(l)


def _seg_bwd_dq_kernel(stats_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, qseg_ref, kseg_ref, qpos_ref, kpos_ref,
                       dq_ref, dq_acc, *, scale, causal, nh, stride,
                       num_k_blocks):
    b = pl.program_id(0) // nh
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = _seg_run_predicate(stats_ref, b * stride + qi, b * stride + ki,
                             causal)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        kk = k_ref[0]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        same = _seg_mask(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal)
        # padding rows carry lse = -1e30; exp(s - lse) there would be 1,
        # so the mask (not the -1e30 trick) must zero p
        p = jnp.where(same, jnp.exp(s - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kk.dtype), kk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _seg_bwd_dkv_kernel(stats_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, qseg_ref, kseg_ref, qpos_ref, kpos_ref,
                        dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                        nh, stride, num_q_blocks):
    b = pl.program_id(0) // nh
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = _seg_run_predicate(stats_ref, b * stride + qi, b * stride + ki,
                             causal)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        kk = k_ref[0]
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        same = _seg_mask(qseg_ref, kseg_ref, qpos_ref, kpos_ref, causal)
        p = jnp.where(same, jnp.exp(s - lse_ref[0]), 0.0)
        do = do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, d]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, d]

    @pl.when(qi == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _seg_views(seg_q, seg_k, pos_q, pos_k):
    """[B, S] int arrays -> the kernel-side layouts: q side [B, Sq, 1]
    (sublane-major, the LSE-block trick), k side [B, 1, Sk]
    (lane-major)."""
    return (jnp.asarray(seg_q, jnp.int32)[:, :, None],
            jnp.asarray(seg_k, jnp.int32)[:, None, :],
            jnp.asarray(pos_q, jnp.int32)[:, :, None],
            jnp.asarray(pos_k, jnp.int32)[:, None, :])


def _seg_specs(nh, group, block_q, block_k, d):
    """The in_specs shared by all three segment kernels, in
    (q, k, v, qseg, kseg, qpos, kpos) order for the given grid layout
    where axis 1 = q blocks, axis 2 = k blocks (the dkv kernel swaps the
    index-map arguments instead)."""
    qtok = pl.BlockSpec((1, block_q, 1),
                        lambda b, i, j, s_, h=nh: (b // h, i, 0))
    ktok = pl.BlockSpec((1, 1, block_k),
                        lambda b, i, j, s_, h=nh: (b // h, 0, j))
    return [
        pl.BlockSpec((1, block_q, d), lambda b, i, j, s_: (b, i, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j, s_, g=group: (b // g, j, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda b, i, j, s_, g=group: (b // g, j, 0)),
        qtok, ktok, qtok, ktok,
    ]


def _seg_fwd(q, k, v, segq, segk, posq, posk, stats, stride, nh, *, scale,
             causal, block_q, block_k, interpret):
    """q: [BH, Sq, D]; k/v: [BKV, Sk, D]; seg/pos in kernel layouts."""
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, nq, nk),
        in_specs=_seg_specs(nh, group, block_q, block_k, d),
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j, s_: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j, s_: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        functools.partial(_seg_fwd_kernel, scale=scale, causal=causal,
                          nh=nh, stride=stride, num_k_blocks=nk),
        name="flash_seg_fwd",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(stats, q, k, v, segq, segk, posq, posk)
    return out, lse


def _seg_bwd(res, g, *, scale, causal, block_q, block_k, interpret):
    (q, k, v, out, lse, segq, segk, posq, posk, stats, stride, nh) = res
    bh, sq, d = q.shape
    bkv, sk, _ = k.shape
    group = bh // bkv
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    do = g.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)              # [BH, Sq, 1]

    def qrow(b, i, j, s_):
        return (b, i, 0)

    row_specs = [pl.BlockSpec((1, block_q, d), qrow),
                 pl.BlockSpec((1, block_q, 1), qrow),
                 pl.BlockSpec((1, block_q, 1), qrow)]

    dq = pl.pallas_call(
        functools.partial(_seg_bwd_dq_kernel, scale=scale, causal=causal,
                          nh=nh, stride=stride, num_k_blocks=nk),
        name="flash_seg_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=(_seg_specs(nh, group, block_q, block_k, d)[:3]
                      + row_specs
                      + _seg_specs(nh, group, block_q, block_k, d)[3:]),
            out_specs=pl.BlockSpec((1, block_q, d), qrow),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(stats, q, k, v, do, lse, delta, segq, segk, posq, posk)

    # dk/dv per query head then group-summed (GQA, same as the dense bwd);
    # grid minor axis iterates q blocks, so every index map swaps (i, j)
    def swap(spec):
        im = spec.index_map
        return pl.BlockSpec(spec.block_shape,
                            lambda b, j, i, s_, f=im: f(b, i, j, s_))

    base = [swap(s) for s in _seg_specs(nh, group, block_q, block_k, d)]
    dk_full, dv_full = pl.pallas_call(
        functools.partial(_seg_bwd_dkv_kernel, scale=scale, causal=causal,
                          nh=nh, stride=stride, num_q_blocks=nq),
        name="flash_seg_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nk, nq),
            in_specs=(base[:3] + [swap(s) for s in row_specs] + base[3:]),
            out_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda b, j, i, s_: (b, j, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, j, i, s_: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(stats, q, k, v, do, lse, delta, segq, segk, posq, posk)

    if group > 1:
        dk = dk_full.reshape(bkv, group, sk, d).sum(axis=1)
        dv = dv_full.reshape(bkv, group, sk, d).sum(axis=1)
    else:
        dk, dv = dk_full, dv_full
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11))
def _flash_seg(q, k, v, seg_q, seg_k, pos_q, pos_k, scale, causal,
               block_q, block_k, interpret):
    out, _ = _flash_seg_fwd(q, k, v, seg_q, seg_k, pos_q, pos_k, scale,
                            causal, block_q, block_k, interpret)
    return out


def _flash_seg_fwd(q, k, v, seg_q, seg_k, pos_q, pos_k, scale, causal,
                   block_q, block_k, interpret):
    b, sq, h, d = q.shape
    qr = _reshape_in(q)
    kr = _reshape_in(k)
    vr = _reshape_in(v)
    segq, segk, posq, posk = _seg_views(seg_q, seg_k, pos_q, pos_k)
    stats, stride = _seg_block_stats(
        jnp.asarray(seg_q, jnp.int32), jnp.asarray(seg_k, jnp.int32),
        jnp.asarray(pos_q, jnp.int32), jnp.asarray(pos_k, jnp.int32),
        block_q, block_k)
    out, lse = _seg_fwd(qr, kr, vr, segq, segk, posq, posk, stats, stride,
                        h, scale=scale, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    res = (qr, kr, vr, out, lse, segq, segk, posq, posk, stats, stride, h)
    return _reshape_out(out, b, h), (res, b, h)


def _flash_seg_bwd(scale, causal, block_q, block_k, interpret, resbh, g):
    res, b, h = resbh
    kvh = res[1].shape[0] // b
    gr = _reshape_in(g)
    dq, dk, dv = _seg_bwd(res, gr, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret)
    return (_reshape_out(dq, b, h), _reshape_out(dk, b, kvh),
            _reshape_out(dv, b, kvh), None, None, None, None)


_flash_seg.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def flash_attention_segments(q, k, v, seg_q, seg_k, pos_q, pos_k, *,
                             causal=False, scale=None,
                             block_q=DEFAULT_BLOCK_Q,
                             block_k=DEFAULT_BLOCK_K, interpret=False):
    """Segment-masked flash attention on [B, S, H, D] packed rows.

    ``seg_q``/``seg_k`` [B, S] int32 tag each token with its document
    (-1 = padding: such rows produce exact zeros and zero gradients);
    tokens attend only within their own segment, and ``causal`` masks on
    the segment-LOCAL positions ``pos_q``/``pos_k`` [B, S] (for
    self-attention packing, pos = offset within the document). GQA and
    the blockwise custom-VJP backward work exactly as in the dense
    ``flash_attention``; additionally, block pairs that can contain no
    visible token pair are skipped via prefetched per-block segment /
    position extrema (see ``count_skipped_blocks`` for the predicate)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    return _flash_seg(q, k, v, jnp.asarray(seg_q, jnp.int32),
                      jnp.asarray(seg_k, jnp.int32),
                      jnp.asarray(pos_q, jnp.int32),
                      jnp.asarray(pos_k, jnp.int32),
                      float(scale), bool(causal), bq, bk, interpret)


def segment_attention_ref(q, k, v, seg_q, seg_k, pos_q, pos_k, *,
                          causal=False, scale=None):
    """Pure-jnp reference with IDENTICAL masking semantics to the
    segment kernels (tier-1's CPU path and the dispatcher fallback):
    same-segment block-diagonal mask, segment-local causal, padding
    (seg < 0) rows exactly zero. GQA contracts grouped heads directly —
    no jnp.repeat of k/v, so KV HBM traffic stays at the kv-head count."""
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    seg_q = jnp.asarray(seg_q, jnp.int32)
    seg_k = jnp.asarray(seg_k, jnp.int32)
    q5 = q.astype(jnp.float32).reshape(b, sq, kvh, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q5,
                   k.astype(jnp.float32)) * scale
    same = ((seg_q[:, :, None] == seg_k[:, None, :])
            & (seg_q[:, :, None] >= 0))                  # [B, Sq, Sk]
    if causal:
        same = same & (jnp.asarray(pos_q)[:, :, None]
                       >= jnp.asarray(pos_k)[:, None, :])
    mask = same[:, None, None]
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(e, axis=-1, keepdims=True)
    l = jnp.where(l == 0.0, 1.0, l)                      # padding rows -> 0
    out = jnp.einsum("bhgqk,bkhd->bqhgd", e / l,
                     v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def segments_supported(q, k, *, block_q=DEFAULT_BLOCK_Q,
                       block_k=DEFAULT_BLOCK_K):
    """Whether the segment kernels handle these shapes (else the
    dispatcher uses segment_attention_ref). Adds the segment-array
    BlockSpec legality (tiling.segment_specs_legal) on top of the dense
    kernel's rules — notably the k-side lane rule: block_k % 128 == 0 or
    block_k == Sk."""
    from .tiling import flash_specs_legal, segment_specs_legal
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    return (sq % bq == 0 and sk % bk == 0 and
            bq % 8 == 0 and bk % 8 == 0 and
            h % k.shape[2] == 0 and d <= 256 and
            flash_specs_legal(b * h, sq, sk, d, bq, bk, q.dtype) and
            segment_specs_legal(b, sq, sk, bq, bk))
