"""Blockwise (memory-efficient) softmax cross-entropy for the LM head.

Reference capability: the fused cross-entropy hot path —
paddle/phi/kernels/gpu/cross_entropy_kernel.cu (softmax+xent in one pass)
and python/paddle/nn/functional/loss.py:2110 margin_cross_entropy's
dedicated kernel route. There the fusion saves a softmax round-trip; here
the win is bigger: the [B*S, V] logits tensor NEVER exists in HBM.

TPU-native design (NOT a port): `lax.scan`s of plain XLA products, under
two differentiation rules chosen from the `reduction` argument.

- forward only (evaluation, `reduction="none"`'s first pass): a scan over
  VOCABULARY chunks. One [N, D] x [D, Vb] product a chunk (bf16 on the
  MXU, f32 accumulation) feeds an online-softmax update (running max `m`,
  running sum-of-exp `s`, gathered gold logit), the recurrence the
  flash-attention kernel uses along K. Peak HBM is O(N * Vb), and no
  gradient product is made for a caller that wants none.
- a reduced loss (`"mean"` / `"sum"`: every train step) is a scalar
  `sum_n w_n * loss_n`, so its cotangent is a scalar and the gradients are
  linear in it: the rule's FORWARD makes them, in one scan over blocks of
  TOKENS. A block's logits product sees the whole head, so its rows'
  log-sum-exp is known before any gradient is made; an inner scan over
  the vocabulary's tiles then makes `d_logits` a tile at a time from the
  block's stored float32 logits, `dx += d_logits @ head[tile]` and
  `dhead[tile] += d_logits^T @ x`: three products, 6*N*D*V, where a scan
  over vocabulary chunks alone has to compute every logit a second time.
  The backward only scales the two gradients by the cotangent.
- `reduction="none"` has a vector cotangent, which only a second pass can
  weigh: its backward recomputes each logit chunk, forms
  d_logits = (softmax - onehot) * g on the fly and contracts it into dx
  and the chunk's dhead rows: four products, 8*N*D*V.

On a v5e the two-pass rule's four products run at 80-95% of the MXU's
peak at V = 102,400 and the one-pass rule's three at 95-96% (PERF.md
sections 5 and 6, PR 34): the loss is compute-bound, not HBM-bound, so a
product saved is a quarter of its time saved, less what the block's
float32 logits cost (written once, read twice: a fifth of the rule's
time). What the blocks buy is memory: vocabularies whose [N, V] logits
would not fit beside the parameters (128k at 16 GB of HBM) train.

Chunking is over STATIC axes, so everything stays fixed-shape for XLA:
`ceil(V / vocab_chunk)` chunks with the tail masked, `ceil(N / Nb)` token
blocks with the tail's rows weighted 0, never a dynamic shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["fused_cross_entropy", "masked_xent_from_logits", "supported"]

_NEG = -1e30   # large-negative instead of -inf: keeps XLA's max/exp exact
               # for masked lanes without generating inf-inf = nan paths


@jax.named_scope("ce")
def masked_xent_from_logits(logits, labels, *, ignore_index: int = -100,
                            reduction: str = "mean"):
    """Materialising xent with the SAME ignore_index semantics as the
    blockwise kernel: ignored / out-of-range labels contribute zero loss
    (and zero gradient), ``mean`` divides by the valid count. The one
    shared definition for every logits-in-HBM call site (dispatcher
    fallback, multi-device llama loss) so the semantics cannot diverge."""
    v = logits.shape[-1]
    valid = (labels != ignore_index) & (labels >= 0) & (labels < v)
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    per = jnp.where(valid, logz - gold, 0.0)
    if reduction == "mean":
        return jnp.sum(per) / jnp.maximum(
            jnp.sum(valid.astype(per.dtype)), 1.0)
    if reduction == "sum":
        return jnp.sum(per)
    return per


def supported(x, head, labels) -> bool:
    """Shape guard for the dispatcher: 2D-flattenable x, matching head."""
    return (x.ndim >= 2 and head.ndim == 2
            and x.shape[-1] == head.shape[-1]
            and labels.shape == x.shape[:-1])


def _pad_head(head, vocab_chunk):
    v = head.shape[0]
    k = -(-v // vocab_chunk)            # ceil
    pad = k * vocab_chunk - v
    if pad:
        head = jnp.pad(head, ((0, pad), (0, 0)))
    return head.reshape(k, vocab_chunk, head.shape[-1]), v


def _chunk_logits(x, head_chunk, base, valid_v):
    """[N, Vb] f32 logits for one head chunk, padded rows masked."""
    logits = jnp.einsum("nd,vd->nv", x, head_chunk,
                        preferred_element_type=jnp.float32)
    vb = head_chunk.shape[0]
    col = base + jnp.arange(vb)
    return jnp.where(col[None, :] < valid_v, logits, _NEG)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _blockwise_ce(x, headc, labels, valid_v):
    """Per-token CE loss [N] from x [N, D], headc [K, Vb, D], labels [N]."""
    loss, _ = _blockwise_ce_fwd(x, headc, labels, valid_v)
    return loss


@jax.named_scope("ce")
def _blockwise_ce_fwd(x, headc, labels, valid_v):
    n = x.shape[0]
    k, vb, _ = headc.shape

    def body(carry, inp):
        m, s, gold = carry
        i, hc = inp
        base = i * vb
        logits = _chunk_logits(x, hc, base, valid_v)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        local = labels - base
        in_chunk = (local >= 0) & (local < vb)
        gl = jnp.take_along_axis(
            logits, jnp.clip(local, 0, vb - 1)[:, None], axis=-1)[:, 0]
        gold = jnp.where(in_chunk, gl, gold)
        return (m_new, s, gold), None

    init = (jnp.full((n,), _NEG, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.full((n,), _NEG, jnp.float32))
    (m, s, gold), _ = lax.scan(body, init, (jnp.arange(k), headc))
    lse = m + jnp.log(s)
    loss = lse - gold
    return loss, (x, headc, labels, lse)


@jax.named_scope("ce")
def _blockwise_ce_bwd(valid_v, res, g):
    x, headc, labels, lse = res
    k, vb, d = headc.shape

    def body(dx, inp):
        i, hc = inp
        base = i * vb
        logits = _chunk_logits(x, hc, base, valid_v)
        p = jnp.exp(logits - lse[:, None])          # masked cols -> ~0
        local = labels - base
        in_chunk = (local >= 0) & (local < vb)
        onehot = (jnp.clip(local, 0, vb - 1)[:, None]
                  == jnp.arange(vb)[None, :]) & in_chunk[:, None]
        d_logits = ((p - onehot.astype(p.dtype)) * g[:, None]).astype(x.dtype)
        dx = dx + jnp.einsum("nv,vd->nd", d_logits, hc,
                             preferred_element_type=jnp.float32)
        dhc = jnp.einsum("nv,nd->vd", d_logits, x,
                         preferred_element_type=jnp.float32)
        return dx, dhc.astype(headc.dtype)

    dx, dheadc = lax.scan(body, jnp.zeros(x.shape, jnp.float32),
                          (jnp.arange(k), headc))
    return dx.astype(x.dtype), dheadc, None


_blockwise_ce.defvjp(_blockwise_ce_fwd, _blockwise_ce_bwd)


# Bytes of float32 logits a token block of the one-pass rule may hold: the
# rule's memory cap, as `vocab_chunk` is the forward-only scan's (beside it
# the float32 [V, D] dhead carry, whatever the block). The chip's sweep at
# N 16,384, D 2,048, V 102,400 chose it (PERF.md section 6, PR 34): blocks
# of 4,096 rows over tiles of 4,096 columns ran the rule in 135.8 ms, of
# 2,048 in 150.0, of 1,024 in 155.1: a larger block reads and writes the
# dhead carry less often and gives the tiles' products fatter operands.
ONEPASS_LOGITS_BYTES = 1 << 31


def token_block(n: int, v: int) -> int:
    """Rows of a token block of the one-pass rule, from the call's shape:
    the largest power of two whose float32 logits ``[Nb, V]`` stay inside
    ONEPASS_LOGITS_BYTES (4,096 at V 102,400), or all ``n`` rows where
    they already do."""
    rows = max(ONEPASS_LOGITS_BYTES // (4 * v), 1)
    return min(1 << rows.bit_length() - 1, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _reduced_ce(x, head, labels, w, vocab_chunk):
    """``sum_n w[n] * loss_n`` from x [N, D], head [V, D], labels [N] and
    per-token weights w [N] (not differentiated). Undifferentiated it is
    the forward-only vocabulary scan."""
    headc, valid_v = _pad_head(head, vocab_chunk)
    loss, _ = _blockwise_ce_fwd(x, headc, labels, valid_v)
    return jnp.sum(loss * w)


@jax.named_scope("ce")
def _reduced_ce_fwd(x, head, labels, w, vocab_chunk):
    from . import _DISPATCH_STATS     # traced only under differentiation
    _DISPATCH_STATS["fused_ce_onepass"] += 1
    n, d = x.shape
    headc, valid_v = _pad_head(head, vocab_chunk)
    kv, vb, _ = headc.shape
    nb = token_block(n, kv * vb)
    k = -(-n // nb)
    pad = k * nb - n
    if pad:             # the tail block's missing rows: weight 0
        x = jnp.pad(x, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad))
        w = jnp.pad(w, (0, pad))

    def body(carry, inp):
        total, dheadc = carry
        xb, lb, wb = inp
        # product 1 sees the whole head, so the rows' log-sum-exp is known
        # before any gradient is made
        logits = _chunk_logits(xb, headc.reshape(kv * vb, d), 0, valid_v)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
        total = total + jnp.sum((lse - gold) * wb)

        def tile(dxb, inp):
            i, hc, dhc = inp
            lt = lax.dynamic_slice_in_dim(logits, i * vb, vb, axis=1)
            p = jnp.exp(lt - lse[:, None])          # masked cols -> 0
            onehot = (lb - i * vb)[:, None] == jnp.arange(vb)[None, :]
            d_logits = ((p - onehot.astype(p.dtype))
                        * wb[:, None]).astype(x.dtype)
            # made once, in memory: left to itself XLA makes it inside
            # both products, once a tile of their outputs (on the chip
            # 143.4 ms a call for 135.8 at these blocks, PERF.md section 6)
            d_logits = lax.optimization_barrier(d_logits)
            dxb = dxb + jnp.einsum("nv,vd->nd", d_logits, hc,
                                   preferred_element_type=jnp.float32)
            dhc = dhc + jnp.einsum("nv,nd->vd", d_logits, xb,
                                   preferred_element_type=jnp.float32)
            return dxb, dhc

        dxb, dheadc = lax.scan(tile, jnp.zeros((nb, d), jnp.float32),
                               (jnp.arange(kv), headc, dheadc))
        return (total, dheadc), dxb

    init = (jnp.zeros((), jnp.float32), jnp.zeros(headc.shape, jnp.float32))
    (total, dheadc), dx = lax.scan(
        body, init, (x.reshape(k, nb, d), labels.reshape(k, nb),
                     w.reshape(k, nb)))
    # float32 until the cotangent has scaled them: one rounding each. The
    # two scalars carry the dtypes the backward has to hand back.
    return total, (dx.reshape(k * nb, d)[:n],
                   dheadc.reshape(kv * vb, d)[:valid_v],
                   jnp.zeros((), x.dtype), jnp.zeros((), head.dtype))


@jax.named_scope("ce")
def _reduced_ce_bwd(vocab_chunk, res, g):
    dx, dhead, x_like, head_like = res
    return ((dx * g).astype(x_like.dtype),
            (dhead * g).astype(head_like.dtype), None, None)


_reduced_ce.defvjp(_reduced_ce_fwd, _reduced_ce_bwd)


@jax.named_scope("ce")
def fused_cross_entropy(x, head, labels, *, vocab_chunk: int = 4096,
                        reduction: str = "mean", ignore_index: int = -100):
    """Softmax cross-entropy of ``x @ head.T`` against integer ``labels``
    without materialising the logits.

    Labels equal to ``ignore_index`` — or out of ``[0, V)`` entirely —
    contribute zero loss and zero gradient, and ``reduction="mean"``
    divides by the number of VALID tokens (the reference
    ``F.cross_entropy`` ignore_index semantics, loss.py). Without this,
    the common -100 padding convention would gather a masked-lane
    ``-1e30`` gold logit and silently poison the mean with ~1e30.

    Args:
      x: [..., D] hidden states (any float dtype; matmuls accumulate f32).
      head: [V, D] output-projection matrix.
      labels: integer [...] gold class ids.
      vocab_chunk: vocab tile size (static; tail chunk masked): of the
        forward-only scan, of ``reduction="none"``, and of the gradient
        products inside a token block (:func:`token_block`) of a
        differentiated ``"mean"`` / ``"sum"``.
      reduction: "mean" | "sum" | "none".
      ignore_index: label value to exclude from loss and gradient.
    """
    if not jnp.issubdtype(jnp.asarray(labels).dtype, jnp.integer):
        # the materialising path's take_along_axis would reject float
        # labels too — don't silently floor soft/smoothed targets
        raise TypeError(
            f"fused_cross_entropy: labels must be integer class ids, got "
            f"{jnp.asarray(labels).dtype} (soft labels are not supported)")
    n = 1
    for s in x.shape[:-1]:
        n *= s
    xf = x.reshape(n, x.shape[-1])
    lf = labels.reshape(n).astype(jnp.int32)
    valid = (lf != ignore_index) & (lf >= 0) & (lf < head.shape[0])
    # invalid rows still compute a (finite) loss against class 0; their
    # weight 0 takes it out of the sum and zeroes their d_logits rows
    lf = jnp.where(valid, lf, 0)
    chunk = min(vocab_chunk, head.shape[0])
    if reduction in ("mean", "sum"):
        w = valid.astype(jnp.float32)
        if reduction == "mean":
            w = w / jnp.maximum(jnp.sum(w), 1.0)
        return _reduced_ce(xf, head, lf, w, chunk)
    # a vector cotangent: the where() zeroes the invalid rows' loss and —
    # through its vjp — their g
    headc, valid_v = _pad_head(head, chunk)
    loss = _blockwise_ce(xf, headc, lf, valid_v)
    return jnp.where(valid, loss, 0.0).reshape(labels.shape)
