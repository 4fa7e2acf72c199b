"""Mixture-of-Experts decoder LM family (DeepSeekMoE / Qwen2-MoE /
ERNIE-4.5-style, the BASELINE.json EP configs).

Reference capability: the PaddleNLP llm/ MoE recipes trained through the
reference's expert-parallel stack (incubate/distributed/models/moe/
moe_layer.py dispatch/combine + gate, fleet expert-parallel groups; the
gate's capacity_factor token dropping lives in
incubate/distributed/models/moe/gate/base_gate.py descendants).
TPU-native design, two dispatch modes:

- "capacity" (single-chip default): GShard capacity-based gather
  dispatch. Token slots scatter into a static [E, C] index grid
  (C = ceil(T*k/E * capacity_factor), lane-aligned), experts run
  batched [E, C, D] matmuls, outputs gather back per (token, k) slot.
  Compute scales with ACTIVE tokens (E*C ~ T*k*factor), not E*T — at
  DeepSeekMoE shapes (E=64, k=6) the dense form burns ~10x the active
  FLOPs. Over-capacity slots drop (token keeps its shared-expert path),
  the reference's capacity_factor semantics.
- "dense" (mesh/EP default): routing becomes two einsums against a
  one-hot combine tensor, so shapes stay static under jit and the expert
  axis shards over the mesh's 'ep' dimension (expert weights are
  [E, ...] arrays with E on 'ep'; XLA turns the dispatch einsum into an
  all-to-all over ICI). Exact (no drops); right when E is small or the
  expert axis is sharded and the einsum IS the a2a.

Fine-grained experts + a shared expert follow the DeepSeekMoE shape;
top-k routing carries the switch-style load-balancing auxiliary loss.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .llama import (_head_logits, _mm, _rms, apply_rope,
                    remat_policy)
from ..core import enforce as E
from ..nn.functional.attention import rope_tables as _rope_tables, sdpa_raw

__all__ = [
    "MoEConfig", "moe_tiny", "deepseek_moe_16b", "qwen2_moe_a14b",
    "ernie_4_5_a3b", "init_params", "forward", "forward_hidden", "loss_fn",
    "param_specs", "make_train_step", "count_params", "adamw_init",
    "moe_capacity", "init_cache", "prefill", "decode_step", "generate",
    "beam_search", "quantize_weights",
]


@dataclasses.dataclass
class MoEConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 1408        # per routed expert
    shared_intermediate_size: int = 2816  # shared-expert MLP width
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 6
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    router_aux_loss_coef: float = 0.001
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full" recomputes everything; "dots" saves matmul outputs (viable
    # with capacity dispatch, where the saved expert activations are
    # C-sized, not T-sized).
    remat_policy: str = "full"
    # None = auto: "capacity" on a single device, "dense" under a mesh
    # (the dense dispatch einsum is what GSPMD lowers to the EP a2a).
    dispatch_mode: Optional[str] = None
    capacity_factor: float = 1.25
    # routing (``_route``): this family's rule is softmax over all the
    # experts and the k picks renormalised, whatever its source's
    # ``norm_topk_prob`` says (the deepseek-moe-16b configuration's stated
    # departure)
    scoring_func: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Blockwise fused CE for the single-device loss (the 102k-vocab
    # logits of the DeepSeekMoE family are ~840M materialized); mesh
    # losses keep the einsum head for vocab-parallel GSPMD sharding.
    fused_ce: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def moe_tiny(**kw) -> MoEConfig:
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                shared_intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=4,
                num_experts=4, num_experts_per_tok=2,
                max_position_embeddings=128, dtype=jnp.float32,
                remat=False, dispatch_mode="dense")
    base.update(kw)
    return MoEConfig(**base)


def deepseek_moe_16b(**kw) -> MoEConfig:
    """DeepSeekMoE-16B shapes (BASELINE config)."""
    base = dict(vocab_size=102400, hidden_size=2048,
                intermediate_size=1408, shared_intermediate_size=2816,
                num_hidden_layers=28, num_attention_heads=16,
                num_key_value_heads=16, num_experts=64,
                num_experts_per_tok=6, max_position_embeddings=4096)
    base.update(kw)
    return MoEConfig(**base)


def qwen2_moe_a14b(**kw) -> MoEConfig:
    """Qwen2-MoE-A14B shapes (BASELINE config)."""
    base = dict(vocab_size=151936, hidden_size=3584,
                intermediate_size=2560, shared_intermediate_size=20480,
                num_hidden_layers=28, num_attention_heads=28,
                num_key_value_heads=4, num_experts=64,
                num_experts_per_tok=8, max_position_embeddings=32768,
                rope_theta=1000000.0)
    base.update(kw)
    return MoEConfig(**base)


def ernie_4_5_a3b(**kw) -> MoEConfig:
    """ERNIE-4.5-style fine-grained MoE shapes (BASELINE north-star
    config family): many small routed experts + an always-on shared
    expert, GQA attention — same structural recipe this MoE core
    implements for DeepSeekMoE."""
    base = dict(vocab_size=103424, hidden_size=2560,
                intermediate_size=1536, shared_intermediate_size=3072,
                num_hidden_layers=28, num_attention_heads=20,
                num_key_value_heads=4, num_experts=64,
                num_experts_per_tok=6, max_position_embeddings=131072,
                rope_theta=500000.0)
    base.update(kw)
    return MoEConfig(**base)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def init_params(config: MoEConfig, key) -> Dict[str, Any]:
    c = config
    hd, nh, nkv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
    L, D, Fe, Fs = (c.num_hidden_layers, c.hidden_size,
                    c.intermediate_size, c.shared_intermediate_size)
    E, V = c.num_experts, c.vocab_size
    ks = jax.random.split(key, 12)

    def nrm(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * 0.02
                ).astype(c.dtype)

    return {
        "embed": nrm(ks[0], (V, D)),
        "layers": {
            "ln1": jnp.ones((L, D), c.dtype),
            "wq": nrm(ks[1], (L, D, nh * hd)),
            "wk": nrm(ks[2], (L, D, nkv * hd)),
            "wv": nrm(ks[3], (L, D, nkv * hd)),
            "wo": nrm(ks[4], (L, nh * hd, D)),
            "ln2": jnp.ones((L, D), c.dtype),
            # router in float32 (routing logits are precision-sensitive)
            "router": jax.random.normal(ks[5], (L, D, E),
                                        jnp.float32) * 0.02,
            # routed experts: [L, E, ...] with E on the ep mesh axis
            "e_gate": nrm(ks[6], (L, E, D, Fe)),
            "e_up": nrm(ks[7], (L, E, D, Fe)),
            "e_down": nrm(ks[8], (L, E, Fe, D)),
            # shared expert (always on — DeepSeekMoE)
            "s_gate": nrm(ks[9], (L, D, Fs)),
            "s_up": nrm(ks[10], (L, D, Fs)),
            "s_down": nrm(ks[11], (L, Fs, D)),
        },
        "ln_f": jnp.ones((D,), c.dtype),
        "lm_head": nrm(jax.random.fold_in(key, 7), (V, D)),
    }


# ---------------------------------------------------------------------------
# MoE block
# ---------------------------------------------------------------------------

def _edeq(w, dtype):
    """Expert-grid weight for the batched einsums: plain array, or the
    weight-only form {"q": int8 [E, in, out], "s": f32 [E, out]} (or
    its packed-int4 sibling {"q4": int8 [E, in/2, out], "s"})
    dequantized into the einsum (the convert fuses under XLA, so HBM
    reads stay int8/int4 — same seam as llama's _mm, including its
    dequant ordering: f32 multiply, ONE cast, so the f32 scale is
    never double-rounded through bf16)."""
    if isinstance(w, dict):
        from .llama import unpack_int4
        q = unpack_int4(w["q4"], -2) if "q4" in w else w["q"]
        return (q.astype(jnp.float32)
                * w["s"][:, None, :]).astype(dtype)
    return w


def quantize_weights(params, weight_dtype: str = "int8"):
    """Weight-only quantization (int8 or packed int4) of a MoE params
    pytree for serving (see llama.quantize_weights). Attention,
    shared-expert, per-expert grids, and the lm head quantize per
    out-channel; the router stays float32 (routing logits are
    precision-sensitive) and the embedding stays full precision
    (gathered, not matmul'd)."""
    from .llama import quant_packed   # the one scheme definition

    out = {"embed": params["embed"], "ln_f": params["ln_f"],
           "layers": {}}
    for name, w in params["layers"].items():
        if name.startswith("ln") or name == "router":
            out["layers"][name] = w
        elif name.startswith("e_"):            # [L, E, in, out]
            out["layers"][name] = quant_packed(
                w, in_axis=2, weight_dtype=weight_dtype)
        else:                                  # [L, in, out]
            out["layers"][name] = quant_packed(
                w, in_axis=1, weight_dtype=weight_dtype)
    out["lm_head"] = quant_packed(params["lm_head"], in_axis=1,
                                  weight_dtype=weight_dtype)
    return out


def moe_capacity(config: MoEConfig, n_tokens: int) -> int:
    """Per-expert slot count: ceil(T*k/E * factor), lane-aligned (128)."""
    c = config
    even = n_tokens * c.num_experts_per_tok / c.num_experts
    cap = int(even * c.capacity_factor + 0.9999)
    return max(8, min(n_tokens, (cap + 127) // 128 * 128 if cap >= 128
                      else cap))


@jax.named_scope("moe.route")
def _route(x, lp, config: MoEConfig):
    """Shared router head: (topv [T,k] f32, topi [T,k], aux).

    The config's routing keys, DeepSeek's names (``MoEConfig``'s defaults
    are this family's own rule: softmax, the top k over all experts,
    always renormalised): ``scoring_func`` ``"softmax"`` or ``"sigmoid"``
    (each expert's score alone); ``n_group`` / ``topk_group``, the experts
    taken ``n_group`` groups in order, a group scored by the sum of its
    two best scores, the picks made among the best ``topk_group`` groups
    only (DeepSeek-V3's group-limited rule); ``norm_topk_prob``, the k
    picked scores over their sum; ``routed_scaling_factor``, times every
    weight. ``models/axk1.py`` takes all four."""
    c = config
    logits = (x.astype(jnp.float32) @ lp["router"])         # [T, E]
    if c.scoring_func == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    choice = probs
    if c.n_group > 1:
        grouped = probs.reshape(probs.shape[:-1] + (c.n_group, -1))
        best = jnp.sum(lax.top_k(grouped, 2)[0], -1)           # [T, groups]
        keep = jax.nn.one_hot(lax.top_k(best, c.topk_group)[1], c.n_group,
                              dtype=jnp.bool_).any(-2)
        choice = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(
            probs.shape)
    topv, topi = lax.top_k(choice, c.num_experts_per_tok)   # [T, k]
    if c.n_group > 1:
        topv = jnp.take_along_axis(probs, topi, -1)
    if c.norm_topk_prob:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)  # renormalize
    if c.routed_scaling_factor != 1.0:
        topv = topv * c.routed_scaling_factor
    # switch-style load-balance aux loss (reference: moe gate aux):
    # fraction of ROUTED token-slots per expert x mean router prob
    sel = jnp.sum(jax.nn.one_hot(topi, c.num_experts, dtype=jnp.float32),
                  axis=1)                                   # [T, E] 0/1
    me = jnp.mean(probs, axis=0)                            # [E]
    ce = jnp.mean(sel, axis=0)
    aux = c.num_experts * jnp.sum(me * ce)
    return topv, topi, aux


@jax.named_scope("moe.experts")
def _expert_ffn(xe, lp):
    """Batched per-expert SwiGLU on [E, C|T, D] slot grids."""
    g = jnp.einsum("ecd,edf->ecf", xe, _edeq(lp["e_gate"], xe.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, _edeq(lp["e_up"], xe.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                      _edeq(lp["e_down"], xe.dtype))


def _rows(a, index):
    """Rows of ``a`` at ``index`` (any shape); zeros where an index is
    ``len(a)`` or more: an empty cell, a dropped slot."""
    return jnp.take(a, index, axis=0, mode="fill", fill_value=0)


def _slot_rows(a, dest):
    """Rows of ``a`` at the slots' cells, slot-major: [k, T, D] for
    ``dest`` [T, k], so that a sum over k adds whole [T, D] slabs (as
    [T, k, D] the rows are copied into a layout that pads k to a tile's
    sublanes)."""
    return _rows(a, dest.T)


# Capacity dispatch's token <-> grid map is a partial permutation: a kept
# slot owns its cell and an occupied cell names its slot. The transpose of
# a gather along it is a gather along its inverse, so the two row
# movements bring their own backward rules and a train step holds no
# scatter-add of rows (XLA's serialises, since rows might collide).
# ``dest`` [T, k]: slot -> cell, E*C for a dropped slot; ``slot_of``
# [E*C]: cell -> flat slot t*k + j, T*k for an empty cell; ``idx``
# [E*C] = slot_of // k: cell -> token, T for an empty cell.

@jax.custom_vjp
def _dispatch_rows(x, idx, dest):
    """xe[cell] = x[idx[cell]]: [T, D] -> [E*C, D]."""
    return _rows(x, idx)


def _dispatch_rows_fwd(x, idx, dest):
    return _rows(x, idx), dest


@jax.named_scope("moe.dispatch")
def _dispatch_rows_bwd(dest, dxe):
    # dx[t] = sum_j dxe[dest[t, j]], in float32 and cast once
    dx = jnp.sum(_slot_rows(dxe, dest).astype(jnp.float32), axis=0)
    return dx.astype(dxe.dtype), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(y, w, idx, slot_of, dest):
    """routed[t] = sum_j w[t, j] * y[dest[t, j]]: [E*C, D] -> [T, D],
    summed in float32."""
    routed = jnp.sum(_slot_rows(y, dest).astype(jnp.float32)
                     * w.T[..., None], axis=0)
    return routed.astype(y.dtype)


def _combine_rows_fwd(y, w, idx, slot_of, dest):
    return _combine_rows(y, w, idx, slot_of, dest), (y, w, idx, slot_of,
                                                     dest)


@jax.named_scope("moe.combine")
def _combine_rows_bwd(res, d):
    y, w, idx, slot_of, dest = res
    # dy[cell] = w[slot_of[cell]] * d[idx[cell]], read from d [T, D]: the
    # [T*k, D] cotangent of the gathered rows never exists
    w_cell = _rows(w.reshape(-1), slot_of)[:, None]
    d_cell = _rows(d, idx).astype(jnp.float32)
    dy = (d_cell * w_cell).astype(y.dtype)
    # dw[t, j] = <y[dest[t, j]], d[t]>, taken cell by cell where both rows
    # already lie, then read by the slots; a dropped slot reads zero
    dw = _rows(jnp.sum(y.astype(jnp.float32) * d_cell, axis=-1), dest)
    return dy, dw.astype(w.dtype), None, None, None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


def _moe_mlp_capacity(x, lp, config: MoEConfig, T):
    """Capacity gather dispatch (single-chip default): compute scales
    with E*C ~ T*k*capacity_factor instead of E*T. Rows move between
    token order and the [E, C] grid by gather in both directions, forward
    and backward, through one pair of index vectors built once a call
    (``slot_of`` / ``idx`` and ``dest``): the only scatter is the int32
    one that builds ``slot_of``."""
    c = config
    E, k = c.num_experts, c.num_experts_per_tok
    C = moe_capacity(c, T)
    topv, topi, aux = _route(x, lp, c)

    with jax.named_scope("moe.dispatch"):
        # Slot bookkeeping in token-major priority order (GShard):
        # pos[t,k] = how many earlier slots chose the same expert ==
        # position in that expert's buffer. Over-capacity slots drop.
        oh = jax.nn.one_hot(topi.reshape(-1), E, dtype=jnp.int32)  # [T*k, E]
        pos = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=-1)  # [T*k]
        expert = topi.reshape(-1)                                   # [T*k]
        keep = pos < C
        dest = jnp.where(keep, expert * C + pos, E * C)             # [T*k]

        # Scatter each kept slot's NUMBER into the [E*C] grid; an empty
        # cell keeps T*k, and so names token T: a zero row to _rows.
        slot_of = jnp.full((E * C,), T * k, jnp.int32).at[dest].set(
            jnp.arange(T * k, dtype=jnp.int32), mode="drop")
        idx = slot_of // k
        dest = dest.reshape(T, k)
        xe = _dispatch_rows(x, idx, dest).reshape(E, C, -1)     # [E, C, D]

    y = _expert_ffn(xe, lp)                                     # [E, C, D]

    with jax.named_scope("moe.combine"):
        # Combine: each (t, k) slot gathers its expert output row, scaled
        # by its (still-normalized) router weight; dropped slots
        # contribute 0.
        w = (topv * keep.reshape(T, k)).astype(jnp.float32)
        routed = _combine_rows(y.reshape(E * C, -1), w, idx, slot_of, dest)
        return routed, aux


def _moe_mlp_dense(x, lp, config: MoEConfig, T, mesh):
    """GShard dense dispatch: combine[t, e] carries top-k router weights;
    expert compute is an einsum over the (sharded) expert axis."""
    c = config
    topv, topi, aux = _route(x, lp, c)
    with jax.named_scope("moe.dispatch"):
        combine = jnp.zeros((T, c.num_experts), jnp.float32).at[
            jnp.arange(T)[:, None], topi].set(topv)         # [T, E]

    constrain = (lambda a, spec: lax.with_sharding_constraint(
        a, NamedSharding(mesh, spec))) if mesh is not None \
        else (lambda a, spec: a)

    # dispatch with the BINARY routing mask (each selected expert sees the
    # unscaled token), combine with the router weights — gates scale
    # expert OUTPUTS, the DeepSeekMoE/GShard semantics (scaling the input
    # of a nonlinear expert would compute a different function)
    with jax.named_scope("moe.dispatch"):
        dispatch = (combine > 0).astype(c.dtype)            # [T, E]
        xe = jnp.einsum("td,te->etd", x.astype(c.dtype), dispatch)
        xe = constrain(xe, P("ep", None, None))
    y = constrain(_expert_ffn(xe, lp), P("ep", None, None))
    with jax.named_scope("moe.combine"):
        routed = jnp.einsum("etd,te->td", y.astype(jnp.float32),
                            combine)                        # weighted combine
        return routed.astype(x.dtype), aux


def _moe_mlp(h, lp, config: MoEConfig, mesh):
    """Top-k routed experts + shared expert. Returns (out, aux_loss)."""
    c = config
    B, S, D = h.shape
    T = B * S
    x = h.reshape(T, D)

    mode = c.dispatch_mode or ("dense" if mesh is not None else "capacity")
    if mode not in ("dense", "capacity"):
        raise E.InvalidArgumentError(
            f"dispatch_mode must be 'dense' or 'capacity', got {mode!r}")
    if mode == "capacity":
        routed, aux = _moe_mlp_capacity(x, lp, c, T)
    else:
        routed, aux = _moe_mlp_dense(x, lp, c, T, mesh)

    with jax.named_scope("moe.shared"):
        sg = _mm(x, lp["s_gate"])
        su = _mm(x, lp["s_up"])
        shared = _mm(jax.nn.silu(sg) * su, lp["s_down"])

    with jax.named_scope("moe.combine"):
        return (routed + shared).reshape(B, S, D).astype(h.dtype), aux


def decode_mlp(x, lp, config: MoEConfig):
    """Post-attention half of a decode-path layer (ln2 + routed/shared
    MoE MLP + residual) — the family seam inference/paged.py composes
    with (see llama.decode_mlp). Router aux loss is dropped: serving
    never backprops."""
    with jax.named_scope("moe.route"):
        h2 = _rms(x, lp["ln2"], config.rms_norm_eps)
    out, _ = _moe_mlp(h2, lp, config, None)
    with jax.named_scope("moe.combine"):
        return x + out


def _head(params, config: MoEConfig):
    """lm-head weight (uniform accessor with llama._head — the MoE
    families never tie embeddings)."""
    return params["lm_head"]


def _block(x, lp, cos, sin, config: MoEConfig, mesh,
           segment_ids=None, positions=None):
    c = config
    B, S, D = x.shape
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim

    with jax.named_scope("attn.proj"):
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
        q = _mm(h, lp["wq"]).reshape(B, S, nh, hd)
        k = _mm(h, lp["wk"]).reshape(B, S, nkv, hd)
        v = _mm(h, lp["wv"]).reshape(B, S, nkv, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    with jax.named_scope("attn.kernel"):
        a = sdpa_raw(q, k, v, is_causal=True, segment_ids=segment_ids,
                     positions=positions).reshape(B, S, nh * hd)
    with jax.named_scope("attn.proj"):
        x = x + _mm(a, lp["wo"])

    with jax.named_scope("moe.route"):
        h = _rms(x, lp["ln2"], c.rms_norm_eps)
    moe_out, aux = _moe_mlp(h, lp, c, mesh)
    with jax.named_scope("moe.combine"):
        return x + moe_out, aux


def forward_hidden(params, ids, config: MoEConfig, *,
                   mesh: Optional[Mesh] = None, segment_ids=None,
                   positions=None):
    """(final hidden [B,S,D] post ln_f, summed aux loss).
    ``segment_ids``/``positions`` [B, S] select sequence-packed
    semantics — segment-masked attention and per-document rope
    positions, exactly as in the llama family."""
    c = config
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], ids, axis=0)
        cos, sin = _rope_tables(ids.shape[1], c.head_dim,
                                theta=c.rope_theta)
        if positions is not None:
            from ..nn.functional.attention import gather_rope_rows
            cos, sin = gather_rope_rows(cos, sin, positions)

    def step(carry, lp):
        y, aux = _block(carry, lp, cos, sin, c, mesh,
                        segment_ids, positions)
        return y, aux

    if c.remat:
        step = jax.checkpoint(step, prevent_cse=False,
                              policy=remat_policy(c.remat_policy))
    x, auxes = lax.scan(step, x, params["layers"])
    with jax.named_scope("head"):
        return _rms(x, params["ln_f"], c.rms_norm_eps), jnp.sum(auxes)


def forward(params, ids, config: MoEConfig, *,
            mesh: Optional[Mesh] = None, segment_ids=None, positions=None):
    """Returns (logits [B,S,V], aux_loss scalar)."""
    x, aux = forward_hidden(params, ids, config, mesh=mesh,
                            segment_ids=segment_ids, positions=positions)
    logits = _head_logits(x, params["lm_head"])
    return logits, aux


# ---------------------------------------------------------------------------
# KV-cache decoding (serving path for the MoE families; same static
# ring-buffer design as models.llama — see the design note there)
# ---------------------------------------------------------------------------

def init_cache(config: MoEConfig, batch: int, max_len: int, dtype=None):
    """Fresh decode cache (same layout as the llama family's)."""
    from .llama import init_cache as _ic
    return _ic(config, batch, max_len, dtype)   # shared field contract


def prefill(params, ids, config: MoEConfig, cache):
    """Consume the prompt [B, S]: fills cache[:, :, :S] and returns
    (cache', last-position logits [B, V])."""
    from .llama import _qkv_proj
    c = config
    B, S = ids.shape
    E.enforce(S <= cache["k"].shape[2],
              f"prompt length {S} exceeds cache max_len "
              f"{cache['k'].shape[2]}")
    x = jnp.take(params["embed"], ids, axis=0)
    cos, sin = _rope_tables(S, c.head_dim, theta=c.rope_theta)

    def step(carry, lp):
        x = carry
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
        q, k, v = _qkv_proj(h, lp, c)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        a = sdpa_raw(q, k, v, is_causal=True).reshape(B, S, -1)
        x = x + _mm(a, lp["wo"])
        h2 = _rms(x, lp["ln2"], c.rms_norm_eps)
        out, _ = _moe_mlp(h2, lp, c, None)
        return x + out, (k, v)

    x, (ks, vs) = lax.scan(step, x, params["layers"])
    kc = lax.dynamic_update_slice(
        cache["k"], ks.astype(cache["k"].dtype), (0,) * 5)
    vc = lax.dynamic_update_slice(
        cache["v"], vs.astype(cache["v"].dtype), (0,) * 5)
    x = _rms(x, params["ln_f"], c.rms_norm_eps)
    logits = _head_logits(x[:, -1, :], params["lm_head"])
    return {"k": kc, "v": vc, "pos": jnp.asarray(S, jnp.int32)}, logits


def decode_step(params, cache, token, config: MoEConfig):
    """One incremental step: ``token`` [B] sits at position cache['pos'].
    Routing runs per decoded token (T = B), so under
    dispatch_mode="capacity" the grid is [E, C] with C =
    moe_capacity(config, B) — typically DROPLESS at small batch, but not
    guaranteed: a slot overflows whenever more than C of the B tokens
    route one of their top-k picks to the same expert (C ~
    ceil(B*k/E * capacity_factor), so a routing hot spot at large B can
    exceed it; only C >= B makes dropping impossible). An over-capacity
    pick silently falls back to the token's shared-expert path, which
    shifts decode logits relative to training. Use dispatch_mode="dense"
    (exact) when serving large batches with skewed routing. Returns
    (cache', logits [B, V])."""
    from .llama import _attn_over_cache, _qkv_proj
    from ..nn.functional.attention import rope_raw
    c = config
    pos = cache["pos"]
    M = cache["k"].shape[2]
    x = jnp.take(params["embed"], token, axis=0)[:, None, :]   # [B, 1, D]
    cos_t, sin_t = _rope_tables(M, c.head_dim, theta=c.rope_theta)
    cos = lax.dynamic_slice_in_dim(cos_t, pos, 1, 0)
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
        h2 = _rms(x, lp["ln2"], c.rms_norm_eps)
        out, _ = _moe_mlp(h2, lp, c, None)
        return x + out, (kc, vc)

    x, (kc, vc) = lax.scan(step, x,
                           (params["layers"], cache["k"], cache["v"]))
    x = _rms(x, params["ln_f"], c.rms_norm_eps)
    logits = _head_logits(x[:, 0, :], params["lm_head"])
    return {"k": kc, "v": vc, "pos": pos + 1}, logits


def generate(params, ids, config: MoEConfig, *, max_new_tokens: int,
             max_len: Optional[int] = None, temperature: float = 0.0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             eos_token_id: Optional[int] = None, pad_token_id: int = 0,
             key=None):
    """Autoregressive generation for the MoE families (greedy /
    temperature / top-k / top-p / EOS stopping); the shared jit-once
    static loop (llama._generate_over)."""
    from .llama import _generate_over
    return _generate_over(
        init_cache, prefill, decode_step, params, ids, config,
        max_new_tokens=max_new_tokens, max_len=max_len,
        temperature=temperature, top_k=top_k, top_p=top_p,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id, key=key)


def beam_search(params, ids, config: MoEConfig, *, max_new_tokens: int,
                num_beams: int, max_len: Optional[int] = None,
                length_penalty: float = 0.0,
                eos_token_id: Optional[int] = None, pad_token_id: int = 0):
    """Static-shape beam search for the MoE families (shared loop —
    see llama.beam_search)."""
    from .llama import _beam_search_over
    return _beam_search_over(
        init_cache, prefill, decode_step, params, ids, config,
        max_new_tokens=max_new_tokens, num_beams=num_beams,
        max_len=max_len, length_penalty=length_penalty,
        eos_token_id=eos_token_id, pad_token_id=pad_token_id)


def loss_fn(params, batch, config: MoEConfig, *,
            mesh: Optional[Mesh] = None):
    """Causal-LM CE + router aux loss. Accepts every llama
    ``unpack_batch`` form, including sequence-packed
    (inp, labels, segment_ids, positions) rows whose labels carry the
    ignore_index at cross-document / padding positions."""
    from .llama import unpack_batch
    inp, labels, seg, pos = unpack_batch(batch)
    c = config
    if c.fused_ce and mesh is None:
        # Blockwise fused CE: the [B,S,V] logits (~840M f32 at the
        # DeepSeekMoE 102k vocab) never materialize in HBM. Same
        # dispatcher as the llama family (autotuned vocab chunk).
        from ..kernels import dispatched_fused_ce

        x, aux = forward_hidden(params, inp, c, mesh=mesh,
                                segment_ids=seg, positions=pos)
        ce = dispatched_fused_ce(x, params["lm_head"], labels)
        return ce + c.router_aux_loss_coef * aux
    logits, aux = forward(params, inp, c, mesh=mesh, segment_ids=seg,
                          positions=pos)
    # the same ignore_index masking as the fused path (packed batches
    # mark cross-document targets and padding with -100)
    from ..kernels.fused_ce import masked_xent_from_logits
    ce = masked_xent_from_logits(logits, labels)
    return ce + c.router_aux_loss_coef * aux


# ---------------------------------------------------------------------------
# sharding + train step
# ---------------------------------------------------------------------------

def param_specs(config: MoEConfig) -> Dict[str, Any]:
    """Placements over a ('dp','fsdp','ep','tp') mesh: expert weights put
    E on 'ep' (expert parallelism) and the expert FFN dims on 'tp'/'fsdp';
    dense weights follow the Megatron/fsdp layout of the llama family."""
    return {
        "embed": P("tp", "fsdp"),
        "layers": {
            "ln1": P(None, None),
            "wq": P(None, "fsdp", "tp"),
            "wk": P(None, "fsdp", "tp"),
            "wv": P(None, "fsdp", "tp"),
            "wo": P(None, "tp", "fsdp"),
            "ln2": P(None, None),
            "router": P(None, "fsdp", None),
            "e_gate": P(None, "ep", "fsdp", "tp"),
            "e_up": P(None, "ep", "fsdp", "tp"),
            "e_down": P(None, "ep", "tp", "fsdp"),
            "s_gate": P(None, "fsdp", "tp"),
            "s_up": P(None, "fsdp", "tp"),
            "s_down": P(None, "tp", "fsdp"),
        },
        "ln_f": P(None),
        "lm_head": P("tp", "fsdp"),
    }


def count_params(config: MoEConfig) -> int:
    import numpy as np
    c = config
    dummy = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(dummy)))


def adamw_init(params):
    from .llama import adamw_init as _ai
    return _ai(params)


def make_train_step(config: MoEConfig, mesh: Optional[Mesh] = None, *,
                    lr: float = 1e-4, donate: bool = True,
                    guard: Optional[bool] = None,
                    numerics: Optional[bool] = None):
    """Jitted AdamW train step; with a mesh, params/opt-state placements
    come from param_specs and the batch shards over ('dp','fsdp').
    Buffer donation updates params/opt-state in place — without it the
    step holds BOTH generations of the expert weights, which at MoE
    sizes is the difference between fitting and OOM.

    ``guard`` (default: ``FLAGS_enable_sentinel``) builds the GUARDED
    4-in/4-out step — identical contract to the llama family's (see
    ``llama.make_train_step``): the update gates on
    ``llama.step_health``'s ok flag behind a ``lax.cond``, anomalous
    steps leave params/opt-state byte-identical, and the health aux
    scalars feed ``training.sentinel``. ``numerics`` (default:
    ``FLAGS_enable_numerics``; guarded step only) adds the in-graph
    per-layer grad statistics block — same contract as the llama
    family's."""
    from .llama import _adamw_update, unpack_batch
    from ..training.guards import (gated_update, grad_numerics,
                                   resolve_guard, resolve_numerics,
                                   step_health)
    guard = resolve_guard(guard)
    numerics = guard and resolve_numerics(numerics)

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, config, mesh=mesh))(params)

    def update(p, o, g):
        return _adamw_update(p, g, o, lr)

    def step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        params, opt_state = update(params, opt_state, grads)
        return params, opt_state, loss

    def guarded_step(params, opt_state, batch, gnorm_cap):
        loss, grads = grads_of(params, batch)
        ok, health = step_health(loss, grads, unpack_batch(batch)[0],
                                 config.vocab_size, gnorm_cap)
        if numerics:
            health["numerics"] = grad_numerics(grads)
        params, opt_state = gated_update(ok, update, params, opt_state,
                                         grads)
        return params, opt_state, loss, health

    dn = (0, 1) if donate else ()
    if mesh is None:
        return jax.jit(guarded_step if guard else step, donate_argnums=dn)

    specs = param_specs(config)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda s: isinstance(s, P))
    bshard = NamedSharding(mesh, P(("dp", "fsdp"), None))

    if guard:
        def placed_guarded(params, opt_state, batch, gnorm_cap):
            params = jax.lax.with_sharding_constraint(params, pshard)
            batch = jax.lax.with_sharding_constraint(batch, bshard)
            return guarded_step(params, opt_state, batch, gnorm_cap)

        return jax.jit(placed_guarded, donate_argnums=dn)

    def placed(params, opt_state, batch):
        params = jax.lax.with_sharding_constraint(params, pshard)
        batch = jax.lax.with_sharding_constraint(batch, bshard)
        return step(params, opt_state, batch)

    return jax.jit(placed, donate_argnums=dn)
