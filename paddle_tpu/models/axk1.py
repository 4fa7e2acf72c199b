"""A.X K1 (SK Telecom, ``model_type`` ``axk1``), a DeepSeek-V3-shaped
model: multi-head latent attention (MLA) over a cache of ONE row a token,
and a sigmoid, group-limited router that sends a token to 8 of 192
experts beside one shared expert. The benchmark's plain reference
(``benchmark/architectures/axk1.py``) has the equations in full.

One layer, pre-norm with a residual round each sublayer:

- attention: ``cq = RMSNorm(h W_DQ)``, ``q = cq W_UQ`` (64 heads of 128
  + 64: ``q_nope``, ``q_pe``); ``[c~ ; kpe] = h W_DKV``, ``c =
  RMSNorm(c~)`` (512), ``k_pe = RoPE(kpe)`` one vector for every head;
  ``[k_nope ; v] = c W_UKV`` (a head: 128 + 128); scores ``(q_nope .
  k_nope + RoPE(q_pe) . k_pe) * scale`` with DeepSeek-V3's YaRN scale;
  ``W_O`` back out.
- the first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``; the others: the shared expert's SwiGLU plus the
  routed experts' weighted sum (``models/expert_parallel.py``, the
  router ``models/moe.py::_route``: sigmoid scores, the best 4 of 8
  groups, the top 8 in them, renormalised, times 2.5).

Serving only, and one chip's share of an expert-parallel deployment: the
chip holds experts ``[first_held_expert, first_held_expert +
n_routed_experts)`` of the router's ``n_router_outputs``, routes every
token over all of them and computes the picks it holds
(``models/expert_parallel.py``, shared with ``models/command_a.py``); the
picks held elsewhere are another chip's (nothing here stands in for them
or for the all-to-all).

The cache is the point. What a token keeps a layer is ``[c ; k_pe]``,
576 numbers, padded to 640 (``pool_layout``: a leaf of one "head" whose
row is five lane tiles, as HBM lays a 576-wide row out anyway): one pool
leaf ``latent``, no values beside it. A prefill decompresses (``k_nope``
and ``v`` a head from ``c``, flash attention, V zero-padded from 128 to
q's and k's 192) and writes the rows; a decode step writes its token's
row in place (``kernels/kv_write.py``) and attends ABSORBED: ``q_lat =
W_UK^T q_nope`` a head, scores against the rows, ``o_lat`` the mix of
the rows' ``c``, ``o = W_UV o_lat`` (``kernels/mla_decode.py``).

The stack is declared (``segments``: the dense layer(s), then the expert
layers, ``models/stack.py``) and run by ``inference/paged.py``'s scan a
segment; the experts' weights lie beside the scanned ones
(``params["experts"]``, read by layer).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..core import enforce as E
from ..nn.functional.attention import (rope_raw, sdpa_reference,
                                       yarn_inv_freq, yarn_mscale)
from .llama import _head_logits, _mm, _rms
from .expert_parallel import routed
from .stack import Segment

__all__ = ["AxK1Config", "axk1_tiny", "init_params", "segments",
           "pool_layout", "stack_block", "final_norm", "forward",
           "prefill_in_passes", "loss_fn"]

# A prefill group is taken a few rows at a time (``inference/paged.py``'s
# passes): the warm-up fills every slot with one group, and a whole group
# of 256 prompts would hold gigabytes of activations beside a full chip.
prefill_in_passes = True

_LANE = 128


@dataclasses.dataclass
class AxK1Config:
    """The source's key names. ``n_routed_experts`` is what this chip
    holds, ``n_router_outputs`` what the router picks among, and
    ``first_held_expert`` where the held run starts."""
    vocab_size: int = 163840
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    num_key_value_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 192
    n_router_outputs: int = 192
    first_held_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "none"
    ep_size: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        E.enforce(self.ep_size == 1,
                  f"ep_size {self.ep_size}: experts across chips (an "
                  f"all-to-all over a mesh, ROADMAP M10) are not written; "
                  f"one chip computes the share it holds",
                  error=E.UnimplementedError)
        E.enforce(self.scoring_func in ("sigmoid", "softmax")
                  and self.topk_method in ("none", "greedy",
                                           "group_limited_greedy")
                  and self.n_shared_experts == 1
                  and not self.tie_word_embeddings,
                  "only the published A.X K1 block is written: sigmoid or "
                  "softmax scores with no selection bias, one shared "
                  "expert, an untied head", error=E.UnimplementedError)
        E.enforce(0 <= self.first_held_expert
                  and self.first_held_expert + self.n_routed_experts
                  <= self.n_router_outputs
                  and self.n_router_outputs % self.n_group == 0,
                  "the held experts must lie inside the router's outputs, "
                  "and the groups divide them")

    @property
    def num_experts(self) -> int:
        """What the router picks among (the engine's expert counters)."""
        return self.n_router_outputs

    @property
    def held(self):
        """The experts this chip computes: [first, first + count)."""
        return (self.first_held_expert,
                self.first_held_expert + self.n_routed_experts)

    @property
    def head_dim(self) -> int:
        """A query's and a key's size a head: ``q_head_dim``."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row(self) -> int:
        """What a token keeps a layer: ``[c ; k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``q_head_dim ** -0.5``, times YaRN's ``mscale(factor,
        mscale_all_dim)`` squared."""
        rs = self.rope_scaling or {}
        m = yarn_mscale(rs.get("factor", 1.0), rs.get("mscale_all_dim", 0)) \
            if rs.get("mscale_all_dim") else 1.0
        return self.head_dim ** -0.5 * m * m

    def inv_freq(self):
        d, rs = self.qk_rope_head_dim, self.rope_scaling
        if not rs:
            return 1.0 / (self.rope_theta ** (
                jnp.arange(0, d, 2, jnp.float32) / d))
        return yarn_inv_freq(
            d, theta=self.rope_theta, factor=rs["factor"],
            original_max_position_embeddings=rs[
                "original_max_position_embeddings"],
            beta_fast=rs.get("beta_fast", 32), beta_slow=rs.get("beta_slow",
                                                                1))

    @property
    def rope_gain(self) -> float:
        """YaRN's factor on cos and sin: ``mscale(factor, mscale) /
        mscale(factor, mscale_all_dim)``."""
        rs = self.rope_scaling or {}
        f = rs.get("factor", 1.0)
        return yarn_mscale(f, rs.get("mscale", 1)) \
            / yarn_mscale(f, rs.get("mscale_all_dim", 1)) if rs else 1.0


def axk1_tiny(**kw) -> AxK1Config:
    """Small config for tests, every ratio that shapes code kept: ranks
    under the width, a small even rope, groups dividing the router's
    outputs, fewer experts held than routed, one dense layer first."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4, q_lora_rank=32,
                kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, n_routed_experts=4, n_router_outputs=16,
                first_held_expert=4, num_experts_per_tok=4, n_group=4,
                topk_group=2, rope_scaling={
                    "type": "yarn", "factor": 8, "mscale": 1,
                    "mscale_all_dim": 1, "beta_fast": 32, "beta_slow": 1,
                    "original_max_position_embeddings": 16},
                max_position_embeddings=256, dtype=jnp.float32)
    base.update(kw)
    return AxK1Config(**base)


def segments(config: AxK1Config):
    """The dense layer(s), then the expert layers; each keeps its rows in
    a layer of the latent pool."""
    k = config.first_k_dense_replace
    return (Segment("dense", k, ("pages",)),
            Segment("moe", config.num_hidden_layers - k, ("pages",)))


def pool_layout(config: AxK1Config):
    """(layers, heads, row, leaves) of the page pool: every layer, one
    "head" of the row ``[c ; k_pe]`` padded to whole lane tiles, ONE leaf
    (no values beside the keys: they are the row's first
    ``kv_lora_rank``)."""
    return (config.num_hidden_layers, 1,
            -(-config.row // _LANE) * _LANE, ("latent",))


def loss_fn(*_, **__):
    raise E.UnimplementedError(
        "A.X K1 has no train step: MLA in training and the expert kernel's "
        "backward are not written, and no cut of the model fits training on "
        "one chip (ISSUE 37)")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(config: AxK1Config, key) -> Dict[str, Any]:
    """Parameter pytree: each segment's layers stacked on axis 0 under its
    name (``dense``, ``moe``), the held experts' three matrices under
    ``experts`` (``[L_moe, E, F, D]``: ``gate`` and ``up`` lie [outputs,
    inputs], ``down`` [inputs, outputs]).

    The tables are ``normal(0, 0.02)`` and every matrix ``normal(0, 1 /
    sqrt(inputs))``, 0.012 for a product of the 7,168-wide stream: each
    product is as large as its input, so that a branch stands to the
    others as in a trained model and none is below what a comparison of
    logits can see (scores of deviation ~2 with the YaRN scale). The two
    low-rank products that a norm follows (``wq_a``, and ``wkv_a``'s
    latent columns, not its rotary ones) are twice that: their outputs
    then stand at twice the norm's, so that a dropped norm doubles a
    score and shows. The experts' ``down`` is twice that too: a token
    takes on average half a held pick here (8 of 192 over the 12 held),
    weighed by about 2.5 / 8, so a held expert would else add a tenth of
    what attention does. The norms' gains are one."""
    c = config
    D, V, H = c.hidden_size, c.vocab_size, c.num_attention_heads
    nope, rope, vd = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    rq, rkv, F, Ex = (c.q_lora_rank, c.kv_lora_rank,
                      c.moe_intermediate_size, c.n_routed_experts)
    counts = {s.kind: s.count for s in segments(c)}
    ks = iter(jax.random.split(key, 32))
    f32 = jnp.float32

    def mat(shape, inputs, times=1.0):
        return (jax.random.normal(next(ks), shape, f32) * times
                * inputs ** -0.5).astype(c.dtype)

    def ones(shape):
        return jnp.ones(shape, c.dtype)

    def attention(n):
        return {"ln1": ones((n, D)),
                "wq_a": mat((n, D, rq), D, 2.0), "q_norm": ones((n, rq)),
                "wq_b": mat((n, rq, H * (nope + rope)), rq),
                "wkv_a": jnp.concatenate([mat((n, D, rkv), D, 2.0),
                                          mat((n, D, rope), D)], -1),
                "kv_norm": ones((n, rkv)),
                "wkv_b": mat((n, rkv, H * (nope + vd)), rkv),
                "wo": mat((n, H * vd, D), H * vd), "ln2": ones((n, D))}

    def swiglu(n, width):
        return {"w_gate": mat((n, D, width), D), "w_up": mat((n, D, width), D),
                "w_down": mat((n, width, D), width)}

    def experts(inputs, times=1.0):
        # a layer at a time: the float32 draw of one stack is 2.8 GB
        return jax.lax.map(
            lambda k: (jax.random.normal(k, (Ex, F, D), f32) * times
                       * inputs ** -0.5).astype(c.dtype),
            jax.random.split(next(ks), counts["moe"]))

    n = counts["moe"]
    return {
        "embed": (jax.random.normal(next(ks), (V, D), f32) * 0.02).astype(
            c.dtype),
        "lm_head": (jax.random.normal(next(ks), (V, D), f32) * 0.02).astype(
            c.dtype),
        "ln_f": ones((D,)),
        "dense": {**attention(counts["dense"]),
                  **swiglu(counts["dense"], c.intermediate_size)},
        "moe": {**attention(n), **swiglu(n, F),
                "router": mat((n, D, c.n_router_outputs), D)},
        "experts": {"gate": experts(D), "up": experts(D),
                    "down": experts(F, 2.0)},
    }


def _head(params, config: AxK1Config):
    return params["lm_head"]


def final_norm(params, x, config: AxK1Config):
    return _rms(x, params["ln_f"], config.rms_norm_eps)


# ---------------------------------------------------------------------------
# multi-head latent attention
# ---------------------------------------------------------------------------

def _rotary(c: AxK1Config, positions):
    """cos, sin [B, S, rope / 2] at ``positions`` [B or 1, S] (YaRN's
    frequencies and gain; DeepSeek's interleaved pairs, ``rope_raw``'s
    ``neox=False``)."""
    ang = positions.astype(jnp.float32)[..., None] * c.inv_freq()
    g = c.rope_gain
    return jnp.cos(ang) * g, jnp.sin(ang) * g


def _inputs(x, lp, c: AxK1Config, cos, sin):
    """ln1 and the low-rank products: (q_nope [B, S, H, nope], q_pe [B, S,
    H, rope] rotated, the row ``[c ; k_pe ; 0]`` [B, S, pool width])."""
    H, nope, rope = c.num_attention_heads, c.qk_nope_head_dim, \
        c.qk_rope_head_dim
    with jax.named_scope("attn.proj"):
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
        cq = _mm(h, lp["wq_a"])
        ckv = _mm(h, lp["wkv_a"])
    cq = _rms(cq, lp["q_norm"], c.rms_norm_eps)
    with jax.named_scope("attn.proj"):
        q = _mm(cq, lp["wq_b"]).reshape(x.shape[:2] + (H, nope + rope))
    q_pe = rope_raw(q[..., nope:], cos, sin, neox=False)
    lat = _rms(ckv[..., :c.kv_lora_rank], lp["kv_norm"], c.rms_norm_eps)
    k_pe = rope_raw(ckv[..., None, c.kv_lora_rank:], cos, sin,
                    neox=False)[..., 0, :]
    pad = pool_layout(c)[2] - c.row
    row = jnp.concatenate(
        [lat, k_pe, jnp.zeros(lat.shape[:-1] + (pad,), lat.dtype)], -1)
    return q[..., :nope], q_pe, row


def _up(lp, c: AxK1Config):
    """``W_UKV`` as [rank, heads, nope + v]: a head's ``W_UK`` and ``W_UV``."""
    return lp["wkv_b"].reshape(c.kv_lora_rank, c.num_attention_heads, -1)


@jax.named_scope("attn.mla")
def _mla(x, lp, c: AxK1Config, layer, ops):
    """Attention's update of ``x`` [B, S, D] (the output product and the
    residual not yet applied: [B, S, H * v]) over pool layer ``layer``."""
    B, S = x.shape[:2]
    nope, r = c.qk_nope_head_dim, c.kv_lora_rank
    pos = jnp.arange(S)[None] if ops.prefill \
        else jnp.maximum(ops.lengths - 1, 0)[:, None]
    q_nope, q_pe, row = _inputs(x, lp, c, *_rotary(c, pos))
    up = _up(lp, c)
    if ops.prefill:
        # decompressed: a head's keys [k_nope ; k_pe], its values padded
        # to the keys' size with zeros (one flash call; the output's
        # padding is dropped)
        lat, k_pe = row[..., :r], row[..., r:c.row]
        with jax.named_scope("attn.proj"):
            kv = jnp.einsum("bsr,rhd->bshd", lat, up.astype(lat.dtype))
        H = kv.shape[2]
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe[:, :, None], (B, S, H, c.qk_rope_head_dim))], -1)
        v = jnp.pad(kv[..., nope:], ((0, 0),) * 3
                    + ((0, c.head_dim - c.v_head_dim),))
        a = ops.attend_latent(row, layer, jnp.concatenate([q_nope, q_pe], -1),
                              k, v, scale=c.softmax_scale)
        return a[..., :c.v_head_dim].reshape(B, S, -1)
    with jax.named_scope("attn.proj"):
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope,
                           up[..., :nope].astype(q_nope.dtype))
    q = jnp.concatenate([q_lat, q_pe, jnp.zeros(
        q_pe.shape[:-1] + (row.shape[-1] - c.row,), q_pe.dtype)], -1)
    o_lat = ops.attend_latent(row, layer, q, scale=c.softmax_scale, rank=r)
    with jax.named_scope("attn.proj"):
        return jnp.einsum("bshr,rhd->bshd", o_lat.astype(x.dtype),
                          up[..., nope:].astype(x.dtype)).reshape(B, S, -1)


# ---------------------------------------------------------------------------
# the feed-forward sublayers
# ---------------------------------------------------------------------------

def _swiglu(h, lp):
    return _mm(jax.nn.silu(_mm(h, lp["w_gate"])) * _mm(h, lp["w_up"]),
               lp["w_down"])


def _moe(x, lp, c: AxK1Config):
    """ln2, the shared expert (``mlp``: dense work every token takes) and
    the held experts' part, the residual: (x', the picks [B, S, k])."""
    B, S, D = x.shape
    with jax.named_scope("mlp"):
        h = _rms(x, lp["ln2"], c.rms_norm_eps)
        shared = _swiglu(h, lp)
    y, e = routed(h.reshape(B * S, D), lp, c,
                  "moe_expert_mlp_decode" if S == 1
                  else "moe_expert_mlp_prefill", lp["layer"])
    with jax.named_scope("moe.combine"):
        x = x + (shared + y.reshape(B, S, D)).astype(x.dtype)
    return x, e.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def stack_block(kind: str, x, lp, config: AxK1Config, i, ops):
    """Layer ``i`` of segment ``kind`` on ``x`` [B, S, D] round the
    program's side of the cache, ``ops`` (``inference/paged.py::
    _StackOps``): its rows in pool layer ``i`` (the dense layers) or
    ``first_k_dense_replace + i``. An expert layer hands back the picks,
    [B, S, k]."""
    c = config
    layer = i if kind == "dense" else c.first_k_dense_replace + i
    a = _mla(x, lp, c, layer, ops)
    with jax.named_scope("attn.proj"):
        x = x + _mm(a.astype(x.dtype), lp["wo"])
    if kind == "dense":
        with jax.named_scope("mlp"):
            return x + _swiglu(_rms(x, lp["ln2"], c.rms_norm_eps), lp)
    return _moe(x, lp, c)


# ---------------------------------------------------------------------------
# whole sequences, no cache
# ---------------------------------------------------------------------------

class _NoCache:
    """``stack_block``'s ``ops`` for whole sequences without a cache:
    plain causal attention on the decompressed heads."""
    prefill = True

    def attend_latent(self, row, layer, q, k=None, v=None, *, scale,
                      rank=None):
        return sdpa_reference(q, k, v, causal=True, scale=scale)


def forward(params, ids, config: AxK1Config, with_routes: bool = False):
    """Logits [B, S, V] of whole sequences [B, S] through the same blocks
    the paged programs run (and, asked for, every expert layer's picks
    [L_moe, B, S, k])."""
    c = config
    ops = _NoCache()
    x = jnp.take(params["embed"], ids, axis=0)
    routes = []
    for seg in segments(c):
        for i in range(seg.count):
            lp = {**jax.tree.map(lambda a: a[i], params[seg.kind]),
                  "experts": params["experts"], "layer": i}
            x = stack_block(seg.kind, x, lp, c, i, ops)
            if isinstance(x, tuple):
                x, e = x
                routes.append(e)
    logits = _head_logits(final_norm(params, x, c), _head(params, c))
    return (logits, jnp.stack(routes)) if with_routes else logits
