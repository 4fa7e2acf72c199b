"""Llama model family — the flagship decoder LM, TPU-first.

Reference capability: the PaddleNLP llm/ Llama recipe trained through the
reference's hybrid-parallel stack (SURVEY.md §6 north star; reference
components: fleet/layers/mpu/mp_layers.py TP layers,
nn/functional/flash_attention.py, incubate fused_rms_norm / fused rope).

TPU-native design — two coupled implementations of the same math:

1. **Functional core** (`init_params` / `forward` / `loss_fn` /
   `make_train_step`): pure JAX over a parameter pytree. Layers are stacked
   along a leading axis and iterated with ``lax.scan`` (one trace for all
   layers — fast compiles at depth), each step wrapped in ``jax.checkpoint``
   (rematerialisation: trade FLOPs for HBM, the reference's recompute
   pass). Sharding is GSPMD: `param_specs` gives per-leaf PartitionSpecs
   over a ('dp','fsdp','tp') mesh (Megatron TP column/row splits expressed
   as weight placements; ZeRO-3 as fsdp sharding), activations constrained
   with `with_sharding_constraint` (sequence-parallel constraint on the
   residual stream when `sp=True`).

2. **Eager Layer model** (`LlamaForCausalLM`): nn.Layer composition for
   imperative training/fine-tuning parity (`model(ids).backward()`), built
   from the framework's RMSNorm/Linear/Embedding layers and the same
   attention kernel seam (F.scaled_dot_product_attention → flash kernel).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import nn
from ..nn import functional as F
from ..core import enforce as E
from ..training.guards import (gated_update, grad_global_norm,
                               grad_numerics, resolve_guard,
                               resolve_numerics, step_health)
from ..nn.functional.attention import (gather_rope_rows as _gather_rope_rows,
                                       rope_raw, rope_tables as _rope_tables,
                                       sdpa_raw)

__all__ = [
    "LlamaConfig", "llama_tiny", "llama_3_8b",
    "init_params", "forward", "loss_fn", "param_specs", "unpack_batch",
    "make_train_step", "make_forward", "adamw_init", "count_params",
    "grad_global_norm",
    "LlamaForCausalLM",
    "init_cache", "prefill", "decode_step", "generate", "make_sampler",
    "beam_search", "quantize_weights", "quant_int8", "quant_packed",
    "unpack_int4",
]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16       # params/activations dtype (MXU-friendly)
    remat: bool = True              # per-layer rematerialisation
    # remat policy: "full" recomputes everything (min HBM); "dots" saves
    # non-batch matmul outputs (reference recompute's selective checkpointing
    # — fewer recomputed FLOPs, higher MFU, modest extra HBM); "attn"
    # saves only the named attention outputs (2*B*S*D bytes/layer) so the
    # backward never re-runs the flash kernel but everything else still
    # rematerialises — the sweet spot when HBM is tight.
    remat_policy: str = "dots"
    # Blockwise lm-head cross entropy (kernels/fused_ce.py): the [B,S,V]
    # logits never hit HBM. Engaged on the single-device path; the GSPMD
    # multi-device loss keeps the einsum head (vocab-parallel sharding of
    # the scan-chunked head is not yet wired).
    fused_ce: bool = True
    # None: the vocab-chunk comes from the autotune cache (measured per
    # shape on TPU). An explicit int is respected verbatim — set it to
    # cap loss-path HBM regardless of what tuning found fastest.
    fused_ce_chunk: Optional[int] = None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama_tiny(**kw) -> LlamaConfig:
    """Small config for tests/dryruns."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=128,
                rope_theta=10000.0, dtype=jnp.float32, remat=False)
    base.update(kw)
    return LlamaConfig(**base)


def llama_3_8b(**kw) -> LlamaConfig:
    """Llama-3-8B shapes (the BASELINE.json north-star recipe)."""
    base = dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                num_hidden_layers=32, num_attention_heads=32,
                num_key_value_heads=8, max_position_embeddings=8192,
                rope_theta=500000.0)
    base.update(kw)
    return LlamaConfig(**base)


# ---------------------------------------------------------------------------
# Functional core
# ---------------------------------------------------------------------------

def init_params(config: LlamaConfig, key) -> Dict[str, Any]:
    """Parameter pytree. Per-layer weights are stacked on axis 0 (scan
    layout). Initialisation mirrors the reference Llama recipe:
    normal(0, 0.02) for projections/embeddings, ones for norms."""
    c = config
    hd, nh, nkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
    L, D, Ff, V = c.num_hidden_layers, c.hidden_size, c.intermediate_size, c.vocab_size
    ks = jax.random.split(key, 8)

    def nrm(k, shape, fan_in):
        std = 0.02
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(c.dtype)

    params = {
        "embed": nrm(ks[0], (V, D), D),
        "layers": {
            "ln1": jnp.ones((L, D), c.dtype),
            "wq": nrm(ks[1], (L, D, nh * hd), D),
            "wk": nrm(ks[2], (L, D, nkv * hd), D),
            "wv": nrm(ks[3], (L, D, nkv * hd), D),
            "wo": nrm(ks[4], (L, nh * hd, D), nh * hd),
            "ln2": jnp.ones((L, D), c.dtype),
            "gate": nrm(ks[5], (L, D, Ff), D),
            "up": nrm(ks[6], (L, D, Ff), D),
            "down": nrm(ks[7], (L, Ff, D), Ff),
        },
        "ln_f": jnp.ones((D,), c.dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = nrm(jax.random.fold_in(key, 99), (V, D), D)
    return params


def remat_policy(name: str):
    """Resolve a config remat-policy name to a jax.checkpoint policy
    (one definition shared by every model family — llama, moe, ...):
    "full" recomputes everything, "dots" saves non-batch matmul outputs,
    "attn" saves only values tagged checkpoint_name("attn_out")."""
    policies = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "attn": jax.checkpoint_policies.save_only_these_names("attn_out"),
        "full": None,
    }
    if name not in policies:
        raise E.InvalidArgumentError(
            f"remat_policy must be one of {sorted(policies)}, got {name!r}")
    return policies[name]


def rope_tables(config: LlamaConfig, seq_len: int, dtype=jnp.float32):
    """cos/sin tables [S, head_dim//2] (shared helper, config theta)."""
    return _rope_tables(seq_len, config.head_dim, theta=config.rope_theta,
                        dtype=dtype)


# rotate-half application shared with the eager op (single rope source)
apply_rope = rope_raw


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _act_spec(sp: bool):
    # residual stream [B, S, D]: batch over dp+fsdp; seq over tp when
    # sequence-parallel (Megatron-SP: norm/elementwise regions run seq-sharded,
    # GSPMD inserts the allgather/reduce-scatter at the matmul boundaries).
    return P(("dp", "fsdp"), "tp" if sp else None, None)


def _noc(a, spec):
    """No-op sharding constraint (single-device paths)."""
    return a


def _mm(x, w):
    """Matmul against a weight that is either a plain array or a
    weight-only-quantized {"q": int8 [in, out], "s": f32 [out]} dict
    (reference: nn/quant weight_only_linear). The dequant fuses into
    the dot under XLA, so HBM reads stay int8 — on the HBM-bound decode
    path that halves the weight traffic.

    Dequant ordering matters for SQNR: the q*s multiply runs in f32
    with ONE cast to the activation dtype. The old
    ``q.astype(bf16) * s.astype(bf16)`` rounded the f32 scale AND the
    product — double rounding that measurably degraded bf16 SQNR
    (caught by the monitor/numerics.py quantization auditor, pinned
    by tests/test_numerics.py)."""
    if isinstance(w, dict):
        q = unpack_int4(w["q4"], -2) if "q4" in w else w["q"]
        return x @ (q.astype(jnp.float32)
                    * w["s"][None, :]).astype(x.dtype)
    return x @ w


@jax.named_scope("head")
def _head_logits(x2d, head):
    """lm-head logits [.., V] from hidden [.., D]; head is [V, D] (or
    its weight-only form {"q": int8 [V, D], "s": f32 [V]})."""
    if isinstance(head, dict):
        # f32 multiply, one cast — the _mm dequant-ordering contract
        q = unpack_int4(head["q4"], -1) if "q4" in head else head["q"]
        w = (q.astype(jnp.float32)
             * head["s"][:, None]).astype(x2d.dtype)
    else:
        w = head
    return jnp.einsum("...d,vd->...v", x2d, w,
                      preferred_element_type=jnp.float32)


def quantize_weights(params, weight_dtype: str = "int8"):
    """Weight-only quantization of a llama params pytree for serving
    (reference: paddle.nn.quant.weight_quantize applied by the
    inference pipelines). Every matmul weight — per-layer attention and
    MLP matrices and the lm head — becomes {"q": int8, "s": f32
    per-out-channel scale} (``weight_dtype="int8"``) or {"q4": two
    int4 nibbles packed per int8 byte along the contraction dim,
    "s": f32} (``weight_dtype="int4"``); the embedding stays full
    precision (it is gathered, not matmul'd; with tied embeddings it
    therefore also serves the head in full precision). The quantized
    tree drops into forward / prefill / decode_step / generate /
    beam_search unchanged — the dequant seams key off the leaf's dict
    shape, a static pytree property."""
    out = {"embed": params["embed"], "layers": {},
           "ln_f": params["ln_f"]}
    for name, w in params["layers"].items():
        if name.startswith("ln"):
            out["layers"][name] = w
            continue
        out["layers"][name] = quant_packed(w, in_axis=1,
                                           weight_dtype=weight_dtype)
    if "lm_head" in params:
        out["lm_head"] = quant_packed(params["lm_head"], in_axis=1,
                                      weight_dtype=weight_dtype)
    return out


def quant_int8(w, in_axis: int):
    """Per-out-channel absmax int8 quantization of a stacked weight:
    the ONE scheme definition every family's quantize_weights and every
    dequant seam (_mm / _edeq / _head_logits) must agree on for the
    quantized-vs-dequantized bit-exact contract. Reduces |w| over
    ``in_axis`` (the contraction dim); returns {"q": int8, "s": f32
    with the reduced axis dropped}."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=in_axis, keepdims=True)
    s = absmax / 127.0
    q = jnp.clip(jnp.round(wf / jnp.maximum(s, 1e-10)),
                 -127, 127).astype(jnp.int8)
    return {"q": q, "s": jnp.squeeze(s, in_axis)}


def quant_packed(w, in_axis: int, weight_dtype: str = "int8"):
    """The family-generic weight-only quantizer: ``quant_int8``
    generalized over the code width under the SAME one-scheme
    per-out-channel absmax contract (reduce |w| over ``in_axis``,
    symmetric scale, round-to-nearest, f32-multiply dequant with ONE
    cast).

    - ``"int8"``: {"q": int8, "s"} — exactly :func:`quant_int8`.
    - ``"int4"``: scale = absmax/7, codes clipped to [-8, 7], then two
      consecutive codes along ``in_axis`` pack into one int8 byte
      (even index -> low nibble, odd -> high nibble — the
      nn/quant weight-only layer's layout): {"q4": int8 with
      ``in_axis`` halved, "s"}. The distinct key name is the STATIC
      marker the dequant seams and the numerics auditor branch on —
      no traced metadata rides the tree."""
    if weight_dtype == "int8":
        return quant_int8(w, in_axis)
    E.enforce_eq(weight_dtype, "int4",
                 "weight-only serving supports int8 and packed int4",
                 error=E.UnimplementedError)
    in_axis = in_axis % w.ndim
    E.enforce(w.shape[in_axis] % 2 == 0,
              f"int4 packing needs an even contraction dim, got "
              f"{w.shape[in_axis]} on axis {in_axis} of {w.shape}")
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=in_axis, keepdims=True)
    s = absmax / 7.0
    q = jnp.clip(jnp.round(wf / jnp.maximum(s, 1e-10)),
                 -8, 7).astype(jnp.int8)
    lo = jax.lax.slice_in_dim(q, 0, None, stride=2, axis=in_axis)
    hi = jax.lax.slice_in_dim(q, 1, None, stride=2, axis=in_axis)
    packed = ((lo & 0x0F) | (hi << 4)).astype(jnp.int8)
    return {"q4": packed, "s": jnp.squeeze(s, in_axis)}


def unpack_int4(q4, in_axis: int):
    """Inverse of :func:`quant_packed`'s int4 nibble pack: sign-extend
    both nibbles of each byte (arithmetic shifts) and re-interleave
    along ``in_axis``, doubling it — int8 codes in [-8, 7], ready for
    the standard f32-multiply dequant. Fuses into the consuming dot
    under XLA, so HBM weight reads stay at 4 bits per value."""
    in_axis = in_axis % q4.ndim
    lo = jnp.left_shift(q4, 4).astype(jnp.int8) >> 4
    hi = q4 >> 4                     # arithmetic: sign-extends
    shape = list(q4.shape)
    shape[in_axis] *= 2
    return jnp.stack([lo, hi], axis=in_axis + 1).reshape(shape)


@jax.named_scope("attn.proj")
def _qkv_proj(h, lp, config: LlamaConfig, constrain=_noc):
    """Attention input projections [B,S,D] -> q/k/v head grids (no rope;
    callers position-encode: training uses the full table, decode the
    gathered row at the cache position). Heads shard over tp inside the
    attention region."""
    c = config
    B, S, _ = h.shape
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = constrain(_mm(h, lp["wq"]).reshape(B, S, nh, hd),
                  P(("dp", "fsdp"), None, "tp", None))
    k = constrain(_mm(h, lp["wk"]).reshape(B, S, nkv, hd),
                  P(("dp", "fsdp"), None, "tp", None))
    v = constrain(_mm(h, lp["wv"]).reshape(B, S, nkv, hd),
                  P(("dp", "fsdp"), None, "tp", None))
    return q, k, v


@jax.named_scope("mlp")
def _ffn(x, lp, config: LlamaConfig, sp: bool = False, constrain=_noc):
    """Post-attention half of a decoder layer (ln2 + SwiGLU + residual)."""
    c = config
    h = _rms(x, lp["ln2"], c.rms_norm_eps)
    g = constrain(_mm(h, lp["gate"]), P(("dp", "fsdp"), None, "tp"))
    u = constrain(_mm(h, lp["up"]), P(("dp", "fsdp"), None, "tp"))
    return x + constrain(_mm(jax.nn.silu(g) * u, lp["down"]),
                         _act_spec(sp))


def decode_mlp(x, lp, config: LlamaConfig):
    """Post-attention half of a decode-path layer (ln2 + SwiGLU +
    residual). The family seam the paged serving path
    (inference/paged.py) composes with: llama and the MoE family expose
    the same signature, so one paged prefill/decode implementation
    serves every decoder family."""
    return _ffn(x, lp, config)


@jax.named_scope("attn.kernel")
def _causal_attention(q, k, v, mesh, segment_ids, positions):
    """Causal attention through the kernel seam (``sdpa_raw``).

    Under a mesh the call runs inside a ``shard_map`` over the batch
    (dp, fsdp) and head (tp) axes — the placement ``_qkv_proj`` already
    constrains q/k/v to: GSPMD cannot partition a Mosaic kernel, and
    attention is independent per batch row and per kv-head group, so
    each device runs the kernel on its own block with no collective.
    The mesh must divide the batch and both head counts (``param_specs``
    presumes the same of the heads): one path on the CPU and the chip."""
    def attend(q, k, v, seg=None, pos=None):
        return sdpa_raw(q, k, v, is_causal=True, segment_ids=seg,
                        positions=pos)

    # positions only matter to a segment mask (sdpa_raw defaults them)
    packed = () if segment_ids is None else tuple(
        a for a in (segment_ids, positions) if a is not None)
    if mesh is None:
        return attend(q, k, v, *packed)
    rows, tp = mesh.shape["dp"] * mesh.shape["fsdp"], mesh.shape["tp"]
    if q.shape[0] % rows or q.shape[2] % tp or k.shape[2] % tp:
        raise ValueError(
            f"mesh {dict(mesh.shape)} does not divide the attention "
            f"shapes: batch {q.shape[0]} must be a multiple of dp*fsdp = "
            f"{rows}, and the {q.shape[2]} query / {k.shape[2]} kv heads "
            f"multiples of tp = {tp}")
    heads = P(("dp", "fsdp"), None, "tp", None)
    tokens = P(("dp", "fsdp"), None)
    return jax.shard_map(
        attend, mesh=mesh, out_specs=heads, check_vma=False,
        in_specs=(heads,) * 3 + (tokens,) * len(packed),
    )(q, k, v, *packed)


def _block(x, lp, cos, sin, config: LlamaConfig, sp: bool, mesh,
           segment_ids=None, positions=None):
    """One decoder layer. x: [B, S, D]; lp: this layer's param slice."""
    c = config
    B, S, D = x.shape
    constrain = (lambda a, spec: lax.with_sharding_constraint(
        a, NamedSharding(mesh, spec))) if mesh is not None else _noc

    with jax.named_scope("attn.proj"):
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
        q, k, v = _qkv_proj(h, lp, c, constrain)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    a = _causal_attention(q, k, v, mesh, segment_ids, positions)
    # Named so remat_policy="attn" can pin exactly this value: the one
    # tensor whose recompute (a full flash-attention forward) dominates
    # the backward pass under full remat, at 2*B*S*D bytes per layer.
    a = checkpoint_name(a, "attn_out")
    with jax.named_scope("attn.proj"):
        a = a.reshape(B, S, -1)
        x = x + constrain(_mm(a, lp["wo"]), _act_spec(sp))
    return _ffn(x, lp, c, sp, constrain)


def forward_hidden(params, ids, config: LlamaConfig, *, sp: bool = False,
                   mesh: Optional[Mesh] = None, segment_ids=None,
                   positions=None):
    """Final hidden states [B, S, D] (post ln_f) from token ids [B, S].

    ``segment_ids``/``positions`` [B, S] select sequence-packed
    semantics: rope positions restart per document and attention is
    segment-masked (see nn.functional.attention.sdpa_raw)."""
    c = config
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], ids, axis=0)
        cos, sin = rope_tables(c, ids.shape[1])
        if positions is not None:
            # segment-local rope rows (sequence packing) via the shared
            # position_ids gather seam
            cos, sin = _gather_rope_rows(cos, sin, positions)

    def step(carry, lp):
        return _block(carry, lp, cos, sin, c, sp, mesh,
                      segment_ids, positions), None

    if c.remat:
        step = jax.checkpoint(step, prevent_cse=False,
                              policy=remat_policy(c.remat_policy))
    x, _ = lax.scan(step, x, params["layers"])
    with jax.named_scope("head"):
        return _rms(x, params["ln_f"], c.rms_norm_eps)


def _head(params, config: LlamaConfig):
    return params["embed"] if config.tie_word_embeddings \
        else params["lm_head"]


def forward(params, ids, config: LlamaConfig, *, sp: bool = False,
            mesh: Optional[Mesh] = None, segment_ids=None, positions=None):
    """Logits [B, S, V] from token ids [B, S]. Pure; jit/shard-ready."""
    x = forward_hidden(params, ids, config, sp=sp, mesh=mesh,
                       segment_ids=segment_ids, positions=positions)
    # logits in float32 for a stable softmax-xent
    return _head_logits(x, _head(params, config))


# ---------------------------------------------------------------------------
# KV-cache decoding (serving path)
#
# Reference capability: incremental decoding via per-layer K/V caches —
# python/paddle/nn/layer/transformer.py MultiHeadAttention.gen_cache /
# Cache (concat-grown) and the PaddleNLP llm generation loops built on
# it. TPU-native design: a STATIC [L, B, max_len, kv, hd] ring buffer
# written with lax.dynamic_update_slice and masked attention — shapes
# never change across steps, so the whole generate loop jits as one
# program (concat-grown caches would retrace/recompile every token).
# ---------------------------------------------------------------------------

def init_cache(config: LlamaConfig, batch: int, max_len: int, dtype=None):
    """Fresh decode cache for ``batch`` sequences of up to ``max_len``."""
    c = config
    dt = dtype if dtype is not None else c.dtype
    shape = (c.num_hidden_layers, batch, max_len, c.num_key_value_heads,
             c.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt),
            "pos": jnp.zeros((), jnp.int32)}


def _attn_over_cache(q, kc, vc, pos):
    """Single-position attention against the cache. q: [B, 1, nh, hd];
    kc/vc: [B, M, nkv, hd]; positions > pos are masked out."""
    B, M, nkv, hd = kc.shape
    nh = q.shape[2]
    g = nh // nkv
    qf = q.astype(jnp.float32).reshape(B, nkv, g, hd)
    scores = jnp.einsum("bkgd,bmkd->bkgm", qf,
                        kc.astype(jnp.float32)) / math.sqrt(hd)
    mask = (jnp.arange(M) <= pos)[None, None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgm,bmkd->bkgd", p, vc.astype(jnp.float32))
    return out.reshape(B, 1, nh * hd)


def prefill(params, ids, config: LlamaConfig, cache):
    """Consume the prompt [B, S]: fills cache[:, :, :S] and returns
    (cache', last-position logits [B, V])."""
    c = config
    B, S = ids.shape
    E.enforce(S <= cache["k"].shape[2],
              f"prompt length {S} exceeds cache max_len "
              f"{cache['k'].shape[2]}")
    x = jnp.take(params["embed"], ids, axis=0)
    cos, sin = rope_tables(c, S)

    def step(carry, lp):
        x = carry
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
        q, k, v = _qkv_proj(h, lp, c)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        a = sdpa_raw(q, k, v, is_causal=True).reshape(B, S, -1)
        x = x + _mm(a, lp["wo"])
        return _ffn(x, lp, c), (k, v)   # cache post-rope k, raw v

    x, (ks, vs) = lax.scan(step, x, params["layers"])
    kc = lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0,) * 5)
    vc = lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0,) * 5)
    x = _rms(x, params["ln_f"], c.rms_norm_eps)
    logits = _head_logits(x[:, -1, :], _head(params, c))
    return {"k": kc, "v": vc, "pos": jnp.asarray(S, jnp.int32)}, logits


def decode_step(params, cache, token, config: LlamaConfig):
    """One incremental step: ``token`` [B] sits at position cache['pos'].
    Returns (cache', logits [B, V]) for the next position."""
    c = config
    pos = cache["pos"]
    M = cache["k"].shape[2]
    x = jnp.take(params["embed"], token, axis=0)[:, None, :]   # [B, 1, D]
    cos_t, sin_t = rope_tables(c, M)
    cos = lax.dynamic_slice_in_dim(cos_t, pos, 1, 0)           # [1, hd/2]
    sin = lax.dynamic_slice_in_dim(sin_t, pos, 1, 0)

    def step(carry, xs):
        x = carry
        lp, kc, vc = xs
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
        q, k, v = _qkv_proj(h, lp, c)
        q = rope_raw(q, cos, sin)
        k = rope_raw(k, cos, sin)
        kc = lax.dynamic_update_slice_in_dim(
            kc, k.astype(kc.dtype), pos, 1)
        vc = lax.dynamic_update_slice_in_dim(
            vc, v.astype(vc.dtype), pos, 1)
        a = _attn_over_cache(q, kc, vc, pos)
        x = x + _mm(a.astype(x.dtype), lp["wo"])
        return _ffn(x, lp, c), (kc, vc)

    x, (kc, vc) = lax.scan(step, x,
                           (params["layers"], cache["k"], cache["v"]))
    x = _rms(x, params["ln_f"], c.rms_norm_eps)
    logits = _head_logits(x[:, 0, :], _head(params, c))
    return {"k": kc, "v": vc, "pos": pos + 1}, logits


def generate(params, ids, config: LlamaConfig, *, max_new_tokens: int,
             max_len: Optional[int] = None, temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             eos_token_id: Optional[int] = None, pad_token_id: int = 0,
             key=None):
    """Autoregressive generation: greedy (temperature 0) or temperature
    sampling with optional top-k / nucleus (top-p) filtering and EOS
    stopping — the reference generation-loop controls (PaddleNLP
    GenerationMixin). ids: [B, S] prompt; returns [B, max_new_tokens];
    with ``eos_token_id`` set, positions after a sequence's EOS hold
    ``pad_token_id`` (the loop itself stays static-shape: finished rows
    keep decoding, their outputs are masked). Jit once, reuse for any
    same-shape prompt."""
    return _generate_over(
        init_cache, prefill, decode_step, params, ids, config,
        max_new_tokens=max_new_tokens, max_len=max_len,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id, key=key)


def _generate_over(init_cache_fn, prefill_fn, decode_fn, params, ids,
                   config, *, max_new_tokens: int,
                   max_len: Optional[int] = None, temperature: float = 0.0,
                   top_k: Optional[int] = None, top_p: Optional[float] = None,
                   eos_token_id: Optional[int] = None, pad_token_id: int = 0,
                   key=None):
    """Family-agnostic sampling loop: any model exposing the
    (init_cache, prefill, decode_step) cache contract plugs in (same
    precedent as _beam_search_over — one copy of the EOS/done logic)."""
    c = config
    B, S = ids.shape
    M = max_len if max_len is not None else S + max_new_tokens
    E.enforce(M >= S + max_new_tokens,
              f"max_len {M} < prompt {S} + max_new_tokens "
              f"{max_new_tokens}")
    if max_new_tokens == 0:
        return jnp.zeros((B, 0), jnp.int32)
    cache = init_cache_fn(c, B, M)
    cache, logits = prefill_fn(params, ids, c, cache)
    sample = make_sampler(temperature, top_k=top_k, top_p=top_p)

    def emit(logits, done, k):
        """One sampling step's token + masked output (shared by the
        scan body and the final carried-logits sample)."""
        tok = sample(logits, k)
        if eos_token_id is not None:
            out = jnp.where(done, jnp.asarray(pad_token_id, jnp.int32),
                            tok)
            done = done | (tok == eos_token_id)
        else:
            out = tok
        return tok, out, done

    def body(carry, k):
        cache, logits, done = carry
        tok, out, done = emit(logits, done, k)
        cache, logits = decode_fn(params, cache, tok, c)
        return (cache, logits, done), out

    keys = jax.random.split(
        key if key is not None else jax.random.PRNGKey(0), max_new_tokens)
    # scan only max_new_tokens-1 decode steps: the final token samples
    # from the carried logits — the last decode's logits were computed
    # and discarded before (one whole step of wasted decode per call)
    (cache, logits, done), toks = lax.scan(
        body, (cache, logits, jnp.zeros((B,), bool)), keys[:-1])
    _, last, _ = emit(logits, done, keys[-1])
    toks = jnp.concatenate([toks, last[None]], axis=0)
    return toks.T                                   # [B, max_new_tokens]


def beam_search(params, ids, config: LlamaConfig, *, max_new_tokens: int,
                num_beams: int, max_len: Optional[int] = None,
                length_penalty: float = 0.0,
                eos_token_id: Optional[int] = None, pad_token_id: int = 0):
    """Static-shape beam search (reference capability: PaddleNLP
    GenerationMixin beam decoding). One prefill, then every step runs
    ONE batched decode over [B*K] beam rows, selects the global top-K of
    ``running score + log-softmax`` over [K, V], and reorders the KV
    cache along the beam axis with a gather — shapes never change, so
    the whole search jits once.

    Finished beams (EOS emitted) are frozen: their only continuation is
    ``pad_token_id`` at zero additional score. Final ranking divides
    scores by ``generated_length ** length_penalty`` (0 = pure
    log-prob). Returns (tokens [B, max_new_tokens] of the best beam,
    best scores [B])."""
    return _beam_search_over(
        init_cache, prefill, decode_step, params, ids, config,
        max_new_tokens=max_new_tokens, num_beams=num_beams,
        max_len=max_len, length_penalty=length_penalty,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id)


def _beam_search_over(init_cache_fn, prefill_fn, decode_fn, params, ids,
                      config, *, max_new_tokens: int, num_beams: int,
                      max_len: Optional[int] = None,
                      length_penalty: float = 0.0,
                      eos_token_id: Optional[int] = None,
                      pad_token_id: int = 0):
    """Family-agnostic beam loop: any model exposing the
    (init_cache, prefill, decode_step) cache contract plugs in (the MoE
    family reuses this verbatim)."""
    c = config
    B, S = ids.shape
    K = num_beams
    E.enforce(K >= 1, f"num_beams must be >= 1, got {K}")
    M = max_len if max_len is not None else S + max_new_tokens
    E.enforce(M >= S + max_new_tokens,
              f"max_len {M} < prompt {S} + max_new_tokens "
              f"{max_new_tokens}")

    cache = init_cache_fn(c, B, M)
    cache, logits = prefill_fn(params, ids, c, cache)   # logits [B, V]
    # replicate the prompt cache across beams: [L, B, ...] -> [L, B*K, ...]
    tile = lambda a: jnp.repeat(a, K, axis=1)
    cache = {"k": tile(cache["k"]), "v": tile(cache["v"]),
             "pos": cache["pos"]}
    V = logits.shape[-1]
    logits = jnp.repeat(logits, K, axis=0)              # [B*K, V]
    # beam 0 starts live, the rest at -inf so step 1 picks K distinct
    # tokens from the prompt distribution
    scores = jnp.tile(jnp.asarray([0.0] + [-jnp.inf] * (K - 1)), (B, 1))
    neg = jnp.asarray(-jnp.inf, jnp.float32)
    if max_new_tokens == 0:
        best0 = jnp.argmax(scores, axis=1)
        return (jnp.zeros((B, 0), jnp.int32),
                jnp.take_along_axis(scores, best0[:, None], axis=1)[:, 0])

    def select(logits, scores, done, lengths):
        """One beam-selection step (pure math over the carried logits);
        shared by the scan body and the final no-decode step."""
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        logp = logp.reshape(B, K, V)
        # frozen beams: only pad continues, at zero additional score
        pad_only = jnp.full((V,), -jnp.inf).at[pad_token_id].set(0.0)
        logp = jnp.where(done[:, :, None], pad_only[None, None, :], logp)
        total = scores[:, :, None] + logp               # [B, K, V]
        top, flat = lax.top_k(total.reshape(B, K * V), K)
        beam_idx, tok = flat // V, (flat % V).astype(jnp.int32)  # [B, K]
        done = jnp.take_along_axis(done, beam_idx, axis=1)
        lengths = jnp.take_along_axis(lengths, beam_idx, axis=1)
        lengths = lengths + (~done).astype(jnp.int32)
        # frozen beams continue through their (possibly wrapped) pad
        # score slot internally, but the RECORDED token is the literal
        # pad id (pad_token_id may be negative, e.g. -1)
        tok = jnp.where(done, jnp.asarray(pad_token_id, jnp.int32), tok)
        if eos_token_id is not None:
            done = done | ((tok == eos_token_id) & ~done)
        return top, tok, beam_idx, done, lengths

    def step(carry, _):
        cache, logits, scores, done, lengths = carry
        scores, tok, beam_idx, done, lengths = select(
            logits, scores, done, lengths)
        gather_rows = (jnp.arange(B)[:, None] * K + beam_idx).reshape(-1)
        cache = {"k": jnp.take(cache["k"], gather_rows, axis=1),
                 "v": jnp.take(cache["v"], gather_rows, axis=1),
                 "pos": cache["pos"]}
        cache, logits = decode_fn(params, cache, tok.reshape(-1), c)
        return (cache, logits, scores, done, lengths), (tok, beam_idx)

    done0 = jnp.zeros((B, K), bool)
    len0 = jnp.zeros((B, K), jnp.int32)
    # scan only max_new_tokens-1 decode steps; the final selection runs
    # on the carried logits with no trailing decode (whose logits were
    # previously computed and thrown away) and no cache reorder
    (cache, logits, scores, done, lengths), (toks, bidx) = lax.scan(
        step, (cache, logits, scores, done0, len0), None,
        length=max_new_tokens - 1)
    scores, tok_f, bidx_f, done, lengths = select(
        logits, scores, done, lengths)
    toks = jnp.concatenate([toks, tok_f[None]], axis=0)
    bidx = jnp.concatenate([bidx, bidx_f[None]], axis=0)

    # Reconstruct each surviving beam's token path by walking the
    # recorded (token, parent-beam) choices backwards.
    def back(carry, xs):
        beam = carry                                    # [B, K]
        tok, bi = xs
        t = jnp.take_along_axis(tok, beam, axis=1)
        beam = jnp.take_along_axis(bi, beam, axis=1)
        return beam, t

    init = jnp.tile(jnp.arange(K), (B, 1))
    _, path = lax.scan(back, init, (toks, bidx), reverse=True)
    path = jnp.moveaxis(path, 0, -1)                    # [B, K, T]

    norm = jnp.maximum(lengths, 1).astype(jnp.float32) ** length_penalty
    best = jnp.argmax(scores / norm, axis=1)            # [B]
    best_toks = jnp.take_along_axis(
        path, best[:, None, None], axis=1)[:, 0, :]
    best_scores = jnp.take_along_axis(scores / norm, best[:, None],
                                      axis=1)[:, 0]
    return best_toks, best_scores


def make_sampler(temperature: float = 0.0, *, top_k: Optional[int] = None,
                 top_p: Optional[float] = None):
    """sample(logits [B, V], key) -> [B] int32: greedy at temperature 0,
    else categorical with optional top-k cut and top-p nucleus filtering
    (the reference generation-loop controls). Static-shape — safe inside
    a jitted decode scan. Shared by every model family's generate."""
    if top_p is not None:
        E.enforce(0.0 < top_p <= 1.0,
                  f"top_p must be in (0, 1], got {top_p}")

    def _filter(logits):
        if top_k is not None:
            kth = lax.top_k(logits, min(top_k, logits.shape[-1]))[0][
                ..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p is not None and top_p < 1.0:
            # drop the tail whose cumulative prob (over descending
            # probs) already exceeded top_p BEFORE this token; the
            # first token always survives
            srt = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1) - probs
            cut = jnp.min(jnp.where(cum < top_p, srt, jnp.inf), axis=-1,
                          keepdims=True)
            logits = jnp.where(logits < cut, -jnp.inf, logits)
        return logits

    def sample(logits, k):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # temperature FIRST, then filter: top-p membership is decided on
        # the tempered distribution (the reference semantics; top-k is
        # invariant to the order, nucleus is not)
        return jax.random.categorical(
            k, _filter(logits / temperature), axis=-1).astype(jnp.int32)

    return sample


def unpack_batch(batch):
    """Normalize a train-step batch to (inp, labels, segment_ids,
    positions) — the ONE accepted-forms definition shared by every model
    family's loss_fn:

    - ids [B, S+1] (labels = shifted ids),
    - (inp, labels),
    - (inp, labels, segment_ids, positions)  — sequence-packed rows,
    - {"ids", "labels", "segment_ids", "positions"} — the packing
      collator's output (io/packing.py): labels are already next-token
      targets with cross-document / padding positions at ignore_index.
    """
    if isinstance(batch, dict):
        return (batch["ids"], batch["labels"],
                batch.get("segment_ids"), batch.get("positions"))
    if isinstance(batch, (tuple, list)):
        if len(batch) == 4:
            return batch[0], batch[1], batch[2], batch[3]
        inp, labels = batch
        return inp, labels, None, None
    return batch[:, :-1], batch[:, 1:], None, None


def loss_fn(params, batch, config: LlamaConfig, *, sp: bool = False,
            mesh: Optional[Mesh] = None):
    """Causal-LM cross entropy. batch = (ids [B,S+1]) or (inp, labels)
    or a sequence-packed form (see ``unpack_batch``): packed rows carry
    per-token segment ids / segment-local positions, and the labels set
    cross-document next-token targets to the fused-CE ignore_index so a
    document never predicts the first token of the next one.

    Single-device: blockwise fused CE (kernels/fused_ce.py) — the [B,S,V]
    logits never materialise in HBM (the reference's
    cross_entropy_kernel.cu capability, rebuilt as an online-softmax scan
    over vocab chunks). Multi-device (mesh): einsum logits + stable xent,
    which GSPMD shards vocab-parallel.
    """
    inp, labels, seg, pos = unpack_batch(batch)
    c = config
    if c.fused_ce and mesh is None:
        from ..kernels import dispatched_fused_ce

        x = forward_hidden(params, inp, c, sp=sp, mesh=mesh,
                           segment_ids=seg, positions=pos)
        return dispatched_fused_ce(x, _head(params, c), labels,
                                   vocab_chunk=c.fused_ce_chunk)
    logits = forward(params, inp, c, sp=sp, mesh=mesh, segment_ids=seg,
                     positions=pos)
    # identical ignore_index masking to the fused path (one shared
    # definition — padded labels zero out, mean over valid tokens)
    from ..kernels.fused_ce import masked_xent_from_logits
    return masked_xent_from_logits(logits, labels)


def param_specs(config: LlamaConfig) -> Dict[str, Any]:
    """GSPMD placement of every weight over a ('dp','fsdp','tp') mesh.
    Megatron column-parallel (wq/wk/wv/gate/up: output dim on tp),
    row-parallel (wo/down: input dim on tp), vocab-parallel embedding &
    head; fsdp (ZeRO-3) shards the other matmul dim."""
    specs = {
        "embed": P("tp", "fsdp"),
        "layers": {
            "ln1": P(None, None),
            "wq": P(None, "fsdp", "tp"),
            "wk": P(None, "fsdp", "tp"),
            "wv": P(None, "fsdp", "tp"),
            "wo": P(None, "tp", "fsdp"),
            "ln2": P(None, None),
            "gate": P(None, "fsdp", "tp"),
            "up": P(None, "fsdp", "tp"),
            "down": P(None, "tp", "fsdp"),
        },
        "ln_f": P(None),
    }
    if not config.tie_word_embeddings:
        specs["lm_head"] = P("tp", "fsdp")
    return specs


def count_params(config: LlamaConfig) -> int:
    c = config
    hd = c.head_dim
    per_layer = (c.hidden_size * hd * (c.num_attention_heads +
                                       2 * c.num_key_value_heads)
                 + c.num_attention_heads * hd * c.hidden_size
                 + 3 * c.hidden_size * c.intermediate_size
                 + 2 * c.hidden_size)
    n = c.vocab_size * c.hidden_size + c.num_hidden_layers * per_layer \
        + c.hidden_size
    if not c.tie_word_embeddings:
        n += c.vocab_size * c.hidden_size
    return n


# -- fused AdamW (the functional-path optimizer; mirrors optimizer/adamw) ---

def adamw_init(params, moment_dtype=jnp.float32):
    """Adam state. moment_dtype=jnp.bfloat16 halves optimizer HBM
    (4 bytes/param for m+v instead of 8) at a small quality cost — the
    update math still runs in f32 (_adamw_update casts up), so only the
    stored moments are rounded."""
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(lambda p: jnp.zeros_like(p, moment_dtype), params),
        "v": jax.tree.map(lambda p: jnp.zeros_like(p, moment_dtype), params),
    }


@jax.named_scope("optim")
def _adamw_update(params, grads, opt_state, lr, *, b1=0.9, b2=0.95,
                  eps=1e-8, wd=0.1):
    step = opt_state["step"] + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def upd(p, g, m, v):
        mdt = m.dtype      # stored moment dtype (f32 or bf16)
        gf = g.astype(jnp.float32)
        m = b1 * m.astype(jnp.float32) + (1 - b1) * gf
        v = b2 * v.astype(jnp.float32) + (1 - b2) * (gf * gf)
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        newp = p.astype(jnp.float32) - lr * (u + wd * p.astype(jnp.float32))
        return newp.astype(p.dtype), m.astype(mdt), v.astype(mdt)

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(opt_state["m"])
    flat_v = tdef.flatten_up_to(opt_state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    newp = tdef.unflatten([o[0] for o in out])
    newm = tdef.unflatten([o[1] for o in out])
    newv = tdef.unflatten([o[2] for o in out])
    return newp, {"step": step, "m": newm, "v": newv}


def make_forward(config: LlamaConfig, mesh: Optional[Mesh] = None):
    """Jitted inference forward. Without a mesh: plain jit (single chip)."""
    if mesh is None:
        return jax.jit(partial(forward, config=config))
    specs = param_specs(config)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    dshard = NamedSharding(mesh, P(("dp", "fsdp"), None))
    return jax.jit(partial(forward, config=config, mesh=mesh),
                   in_shardings=(pshard, dshard),
                   out_shardings=NamedSharding(mesh, P(("dp", "fsdp"), None, "tp")))


def make_train_step(config: LlamaConfig, mesh: Optional[Mesh] = None, *,
                    lr: float = 3e-4, weight_decay: float = 0.1,
                    sp: bool = False, donate: bool = True,
                    guard: Optional[bool] = None,
                    numerics: Optional[bool] = None):
    """Build `(params, opt_state, batch) -> (params, opt_state, loss)`.

    With a mesh (axes 'dp','fsdp','tp'): full GSPMD hybrid parallelism —
    dp/fsdp batch sharding, ZeRO-3 param+opt-state sharding on fsdp,
    Megatron TP on tp, optional sequence parallel. Buffer donation keeps
    params/opt-state in place (no 2x HBM). The batch may be any
    ``unpack_batch`` form — the single batch sharding below is a pytree
    PREFIX, so a packed (inp, labels, segment_ids, positions) tuple (all
    [B, S]) shards each leaf over ('dp','fsdp') without new plumbing.

    ``guard`` (default: ``FLAGS_enable_sentinel``) selects the GUARDED
    step `(params, opt_state, batch, gnorm_cap) -> (params, opt_state,
    loss, health)`: the optimizer update sits behind a ``lax.cond`` on
    :func:`step_health`'s ok flag, so an anomalous batch (non-finite
    loss/grads, out-of-range token ids, grad norm over the host-fed
    ``gnorm_cap`` scalar) leaves params and opt-state byte-identical —
    all-or-nothing ON DEVICE, donation and shardings intact — and
    ``health`` = {"finite", "grad_norm"} feeds the host-side
    ``training.sentinel`` policy engine. Unguarded (the default with
    the flag off), the step is exactly the 3-in/3-out program above:
    zero extra device outputs.

    ``numerics`` (default: ``FLAGS_enable_numerics``; guarded step
    only) adds ``health["numerics"]`` — the in-graph per-layer tensor
    statistics of the gradients (``training.guards.grad_numerics``:
    absmax/rms/mean/zero fraction, overflow/underflow fraction vs
    dtype range, and the per-layer grad-norm breakdown whose squared
    entries sum to ``grad_norm``) as fused reductions in the SAME
    compiled program. Off (the default) the guarded step is
    byte-identical to the pre-numerics program."""
    guard = resolve_guard(guard)
    numerics = guard and resolve_numerics(numerics)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, config, sp=sp, mesh=mesh))(params)

    def update(p, o, g):
        return _adamw_update(p, g, o, lr, wd=weight_decay)

    def step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        params, opt_state = update(params, opt_state, grads)
        return params, opt_state, loss

    def guarded_step(params, opt_state, batch, gnorm_cap):
        loss, grads = grads_of(params, batch)
        ok, health = step_health(loss, grads, unpack_batch(batch)[0],
                                 config.vocab_size, gnorm_cap)
        if numerics:
            # fused per-layer reductions over the grads the step already
            # holds — same program, small f32 aux outputs
            health["numerics"] = grad_numerics(grads)
        params, opt_state = gated_update(ok, update, params, opt_state,
                                         grads)
        return params, opt_state, loss, health

    dn = (0, 1) if donate else ()
    if mesh is None:
        return jax.jit(guarded_step if guard else step, donate_argnums=dn)

    specs = param_specs(config)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
    oshard = {"step": NamedSharding(mesh, P()), "m": pshard, "v": pshard}
    dshard = NamedSharding(mesh, P(("dp", "fsdp"), None))
    scalar = NamedSharding(mesh, P())
    if guard:
        # the health aux scalars replicate; with numerics on, `scalar`
        # acts as a pytree PREFIX covering the whole stats subtree
        # (every entry is a replicated scalar or [L] row). Without
        # numerics the explicit dict keeps the program byte-identical
        # to the pre-numerics one.
        hshard = scalar if numerics else {"finite": scalar,
                                          "grad_norm": scalar}
        return jax.jit(
            guarded_step,
            in_shardings=(pshard, oshard, dshard, scalar),
            out_shardings=(pshard, oshard, scalar, hshard),
            donate_argnums=dn)
    return jax.jit(step,
                   in_shardings=(pshard, oshard, dshard),
                   out_shardings=(pshard, oshard, scalar),
                   donate_argnums=dn)


def shard_params(params, config: LlamaConfig, mesh: Mesh):
    """Place an (initialised) param pytree onto the mesh per param_specs."""
    specs = param_specs(config)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)),
        params, specs,
        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Eager Layer model (imperative parity path)
# ---------------------------------------------------------------------------

class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.config = c
        self.input_layernorm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.q_proj = nn.Linear(c.hidden_size,
                                c.num_attention_heads * c.head_dim,
                                bias_attr=False)
        self.k_proj = nn.Linear(c.hidden_size,
                                c.num_key_value_heads * c.head_dim,
                                bias_attr=False)
        self.v_proj = nn.Linear(c.hidden_size,
                                c.num_key_value_heads * c.head_dim,
                                bias_attr=False)
        self.o_proj = nn.Linear(c.num_attention_heads * c.head_dim,
                                c.hidden_size, bias_attr=False)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   epsilon=c.rms_norm_eps)
        self.gate_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                   bias_attr=False)
        self.up_proj = nn.Linear(c.hidden_size, c.intermediate_size,
                                 bias_attr=False)
        self.down_proj = nn.Linear(c.intermediate_size, c.hidden_size,
                                   bias_attr=False)

    def forward(self, x, cos, sin):
        from .. import ops
        c = self.config
        b, s = x.shape[0], x.shape[1]
        h = self.input_layernorm(x)
        q = ops.reshape(self.q_proj(h),
                        shape=[b, s, c.num_attention_heads, c.head_dim])
        k = ops.reshape(self.k_proj(h),
                        shape=[b, s, c.num_key_value_heads, c.head_dim])
        v = ops.reshape(self.v_proj(h),
                        shape=[b, s, c.num_key_value_heads, c.head_dim])
        q = F.apply_rotary_emb(q, cos, sin)
        k = F.apply_rotary_emb(k, cos, sin)
        a = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        a = ops.reshape(a, shape=[b, s, c.num_attention_heads * c.head_dim])
        x = x + self.o_proj(a)
        h = self.post_attention_layernorm(x)
        x = x + self.down_proj(F.silu(self.gate_proj(h)) * self.up_proj(h))
        return x


class LlamaForCausalLM(nn.Layer):
    """Imperative Llama (reference surface: PaddleNLP LlamaForCausalLM)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = nn.Embedding(c.vocab_size, c.hidden_size)
        self.layers = nn.LayerList(
            [LlamaDecoderLayer(c) for _ in range(c.num_hidden_layers)])
        self.norm = nn.RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        if not c.tie_word_embeddings:
            self.lm_head = nn.Linear(c.hidden_size, c.vocab_size,
                                     bias_attr=False)

    def forward(self, ids):
        from .. import ops
        c = self.config
        x = self.embed_tokens(ids)
        s = ids.shape[1]
        cos, sin = rope_tables(c, s)
        for layer in self.layers:
            x = layer(x, cos, sin)
        x = self.norm(x)
        if c.tie_word_embeddings:
            return ops.matmul(x, ops.transpose(self.embed_tokens.weight,
                                               perm=[1, 0]))
        return self.lm_head(x)

    _LAYER_MAP = (("ln1", "input_layernorm"), ("wq", "q_proj"),
                  ("wk", "k_proj"), ("wv", "v_proj"), ("wo", "o_proj"),
                  ("ln2", "post_attention_layernorm"),
                  ("gate", "gate_proj"), ("up", "up_proj"),
                  ("down", "down_proj"))

    def functional_params(self):
        """This Layer's weights as the functional-core pytree
        (init_params layout) — the bridge onto the jitted train/decode
        paths. Values are snapshots: mutate the Layer, re-export."""
        c = self.config
        layers = {
            fk: jnp.stack([jnp.asarray(getattr(l, attr).weight.numpy())
                           for l in self.layers])
            for fk, attr in self._LAYER_MAP}
        params = {"embed": jnp.asarray(self.embed_tokens.weight.numpy()),
                  "layers": layers,
                  "ln_f": jnp.asarray(self.norm.weight.numpy())}
        if not c.tie_word_embeddings:
            # functional head is [V, D]; nn.Linear stores [D, V]
            params["lm_head"] = jnp.asarray(self.lm_head.weight.numpy()).T
        return params

    def generate(self, ids, max_new_tokens: int, num_beams: int = 1,
                 **kw):
        """Autoregressive generation through the static-cache functional
        path (see module-level ``generate``; ``num_beams > 1`` selects
        beam search, the reference's one-generate-API shape). Accepts
        array or Tensor ids; returns a Tensor [B, max_new_tokens]."""
        from ..core.tensor import to_tensor

        arr = ids.numpy() if hasattr(ids, "numpy") else np.asarray(ids)
        args = (self.functional_params(), jnp.asarray(arr, jnp.int32),
                self.config)
        if num_beams > 1:
            # the GenerationMixin-style surface accepts both kwarg sets;
            # beam search is deterministic, so sampling knobs are
            # silently inapplicable (reference behavior) — drop them
            for k in ("temperature", "top_k", "top_p", "key"):
                kw.pop(k, None)
            toks, _ = beam_search(*args, max_new_tokens=max_new_tokens,
                                  num_beams=num_beams, **kw)
        else:
            kw.pop("length_penalty", None)   # beam-only knob
            toks = generate(*args, max_new_tokens=max_new_tokens, **kw)
        return to_tensor(np.asarray(toks))
