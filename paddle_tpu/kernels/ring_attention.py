"""Ring attention — exact long-context attention over a sequence axis.

Reference capability: the SEP topology axis + SP utilities (SURVEY.md §5
"Long context": the reference scales sequence with SEP/SP + recompute but
has no ring/Ulysses kernels — this module *exceeds* reference parity, as
SURVEY.md §2.6 SEP row calls for).

TPU-native design: the sequence is sharded over a mesh axis ('sp'); each
device holds q/k/v chunks [B, S/n, H, D]. A `lax.scan` over n ring steps
rotates the k/v chunk with `lax.ppermute` (ICI collective-permute — the
ring rides neighbor links, overlapping comm with the chunk's attention
math) while an online-softmax accumulator (m, l, acc) merges each chunk's
contribution — flash attention across devices. Causality is enforced with
global position masks, so the result is *exactly* standard causal
attention on the full sequence. Fully differentiable (AD through the scan
reverses the ring)."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention"]

_NEG_INF = -1e30


def _chunk_attn(q, k, v, row0, col0, *, scale, causal):
    """One q-chunk × one kv-chunk partial attention.
    q: [B, Sq, H, D], k/v: [B, Sk, H, D] (heads already matched).
    Returns (scores_exp_sum l [B,H,Sq,1], row max m [B,H,Sq,1],
    weighted values acc [B,H,Sq,D])."""
    qt = jnp.swapaxes(q, 1, 2)          # [B,H,Sq,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(
            jnp.int32, s.shape[-2:], 0)
        cols = col0 + jax.lax.broadcasted_iota(
            jnp.int32, s.shape[-2:], 1)
        s = jnp.where(rows[None, None] >= cols[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)                    # [B,H,Sq,1]
    # guard fully-masked chunks (m = -inf): shift by 0 there
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
    return m_safe, l, acc


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                   causal: bool = True, scale: Optional[float] = None):
    """Exact attention over sequence sharded on ``axis``.

    q/k/v: [B, S, H, D] global arrays (S sharded over ``axis``); returns
    [B, S, H, D] with the same sharding. GQA supported (kv heads divide q
    heads)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = mesh.shape[axis]
    s_local = q.shape[1] // n
    h, kvh = q.shape[2], k.shape[2]
    group = h // kvh

    def local(qc, kc, vc):
        # qc/kc/vc: local chunks [B, S/n, H(or KV), D]
        if group > 1:
            kc = jnp.repeat(kc, group, axis=2)
            vc = jnp.repeat(vc, group, axis=2)
        idx = lax.axis_index(axis)
        my_row0 = idx * s_local

        def ring_step(carry, t):
            kck, vck, m, l, acc = carry
            # kv chunk currently held came from device (idx - t) mod n
            src = (idx - t) % n
            col0 = src * s_local
            mc, lc, ac = _chunk_attn(qc, kck, vck, my_row0, col0,
                                     scale=scale, causal=causal)
            m_new = jnp.maximum(m, mc)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(mc - m_new)
            l_new = l * alpha + lc * beta
            acc_new = acc * alpha + ac * beta
            # rotate kv to the next device (ring)
            perm = [(i, (i + 1) % n) for i in range(n)]
            kck = lax.ppermute(kck, axis, perm)
            vck = lax.ppermute(vck, axis, perm)
            return (kck, vck, m_new, l_new, acc_new), None

        b, sl = qc.shape[0], qc.shape[1]
        m0 = jnp.full((b, h, sl, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, sl, 1), jnp.float32)
        a0 = jnp.zeros((b, h, sl, qc.shape[-1]), jnp.float32)
        (_kf, _vf, m, l, acc), _ = lax.scan(
            ring_step, (kc, vc, m0, l0, a0), jnp.arange(n))
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc / l).astype(qc.dtype)
        return jnp.swapaxes(out, 1, 2)   # [B, S/n, H, D]

    spec = P(None, axis, None, None)
    return shard_map(local, mesh=mesh,
                     in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
