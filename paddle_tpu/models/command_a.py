"""Command A+ (Cohere, ``model_type`` ``cohere2_moe``): a parallel block
(one LayerNorm feeds attention and the expert sublayer, one residual sum
takes both), three layers of window attention with interleaved (GPT-J)
rope to every layer of full attention without any position encoding, and
in every layer 8 of 128 experts picked by a sigmoid router beside four
shared experts whose sum is averaged with the routed one. The benchmark's
plain reference (``benchmark/architectures/command_a.py``) has the
equations in full. One layer:

- ``h = LayerNorm(x)``: mean-centred, a weight and no bias, statistics in
  float32;
- window layers: ``q = h Wq``, ``k = h Wk``, ``v = h Wv`` (128 query heads
  over 8 key/value heads of 128), q and k rotated by interleaved rope,
  position t attends to t - window + 1 .. t; the full layer: the same
  without rotation over the whole context; ``o Wo``;
- ``s = sigmoid(h W_r)`` over the router's outputs (float32), the 8 best
  picked and weighed ``s_i / sum of the picked s``; ``routed = sum of
  w_i SwiGLU_i(h)``; ``shared = SwiGLU_shared(h)``, the four shared
  experts side by side as one SwiGLU four times an expert's width;
- ``x' = x + o Wo + (routed + shared) / 2``.

The head is ``LayerNorm(x) table^T * logit_scale`` over the tied table.

Serving only, and one chip's share of an expert-parallel deployment: the
chip holds experts ``[first_held_expert, first_held_expert +
n_routed_experts)`` of the router's ``n_router_outputs``, routes every
token over all of them and computes the picks it holds
(``models/expert_parallel.py``, the layer ``models/axk1.py`` computes its
share with); the picks held elsewhere are another chip's, and nothing
here stands in for them or for the all-to-all.

The stack is declared (``segments``: ``layer_switch - 1`` window layers
then a full layer, once a period, ``models/stack.py``) and run by
``inference/paged.py``'s scan a segment. What a sequence keeps: for each
window layer a ring of ``sliding_window + ring_page`` keys and values
(position p at slot p mod the ring; keys are stored after their
rotation, so the order inside the ring does not matter, only which slots
are valid), read by the paged kernel's window form; for each full layer
its pages in the pool. The experts' weights lie beside the scanned ones
(``params["experts"]``, read by layer).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import enforce as E
from ..nn.functional.attention import rope_raw, sdpa_reference
from .expert_parallel import EXPERT_TOKENS, pieces, routed
from .llama import _head_logits, _mm
from .stack import Segment

__all__ = ["CommandAConfig", "command_a_tiny", "init_params", "segments",
           "pool_layout", "state_shapes", "stack_block", "final_norm",
           "forward", "loss_fn"]

_WINDOW, _FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class CommandAConfig:
    """The source's key names, but for ``n_routed_experts``: the experts
    this chip holds (the source's ``num_experts`` is what the router picks
    among, ``n_router_outputs`` here); ``first_held_expert`` is where the
    held run starts. ``ring_page`` (tokens a ring holds past the window),
    ``ep_size`` and ``vision_config`` are the family's own."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # an expert's width
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Optional[Tuple[str, ...]] = None
    layer_switch: int = 4
    order_of_interleaved_layers: str = "local_attn_first"
    sliding_window: int = 4096
    layer_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    position_embedding_type: str = "rope_gptj"
    rotary_pct: float = 1.0
    n_routed_experts: int = 128
    n_router_outputs: int = 128
    first_held_expert: int = 0
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    shared_expert_combination_strategy: str = "average"
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 0
    use_parallel_block: bool = True
    use_qk_norm: bool = False
    use_gated_activation: bool = True
    hidden_act: str = "silu"
    attention_bias: bool = False
    logit_scale: float = 1.0
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 200000
    ep_size: int = 1
    vision_config: Optional[dict] = None
    ring_page: int = 16
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L, n = self.num_hidden_layers, self.layer_switch
        if self.layer_types is None:
            self.layer_types = tuple(
                _FULL if (i + 1) % n == 0 else _WINDOW for i in range(L))
        self.layer_types = tuple(self.layer_types)
        E.enforce(self.ep_size == 1,
                  f"ep_size {self.ep_size}: experts across chips (an "
                  f"all-to-all over a mesh, ROADMAP M10) are not written; "
                  f"one chip computes the share it holds",
                  error=E.UnimplementedError)
        E.enforce(self.vision_config is None,
                  "the vision tower: its sizes are not in the source's "
                  "text configuration, and only text tokens are served",
                  error=E.UnimplementedError)
        period = (_WINDOW,) * (n - 1) + (_FULL,)
        E.enforce(n >= 2 and L % n == 0
                  and self.layer_types[:L] == period * (L // n)
                  and self.order_of_interleaved_layers == "local_attn_first",
                  f"only the published stack is written: layer_switch - 1 "
                  f"window layers then one full layer, a whole number of "
                  f"times (layer_types {self.layer_types[:L]})",
                  error=E.UnimplementedError)
        E.enforce(self.use_parallel_block and not self.use_qk_norm
                  and self.use_gated_activation and self.hidden_act == "silu"
                  and not self.attention_bias
                  and self.first_k_dense_replace == 0
                  and self.expert_selection_fn in ("sigmoid", "softmax")
                  and self.shared_expert_combination_strategy == "average"
                  and self.position_embedding_type == "rope_gptj"
                  and self.rotary_pct == 1 and self.tie_word_embeddings,
                  "only the published Command A+ block is written: a "
                  "parallel block, SwiGLU experts, no bias, no prefix dense "
                  "layer, shared experts averaged in, interleaved rope on "
                  "the whole head, a tied table",
                  error=E.UnimplementedError)
        E.enforce(0 <= self.first_held_expert
                  and self.first_held_expert + self.n_routed_experts
                  <= self.n_router_outputs,
                  "the held experts must lie inside the router's outputs")
        E.enforce(self.sliding_window % self.ring_page == 0
                  and self.num_attention_heads % self.num_key_value_heads
                  == 0, "ring pages divide the window; key/value heads the "
                  "query heads")

    # -- the router's rule, in the names models/moe.py::_route reads --------

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
    def scoring_func(self) -> str:
        return self.expert_selection_fn

    n_group = topk_group = 1                  # no groups
    routed_scaling_factor = 1.0

    @property
    def ring_tokens(self) -> int:
        return self.sliding_window + self.ring_page

    @property
    def periods(self) -> int:
        return self.num_hidden_layers // self.layer_switch


def command_a_tiny(**kw) -> CommandAConfig:
    """Small config for tests, every ratio that shapes code kept: one
    period of three window layers and a full layer, grouped-query heads,
    a window of 8 in a ring of 12 (pages of 4), 16 router outputs of which
    this "chip" holds 4 from the fifth, 4 picks a token, 4 shared experts
    beside them."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                num_hidden_layers=4, num_attention_heads=8,
                num_key_value_heads=2, head_dim=16, sliding_window=8,
                ring_page=4, n_routed_experts=4, n_router_outputs=16,
                first_held_expert=4, num_experts_per_tok=4,
                max_position_embeddings=256, dtype=jnp.float32)
    base.update(kw)
    return CommandAConfig(**base)


# ---------------------------------------------------------------------------
# the one declaration of the stack
# ---------------------------------------------------------------------------

def segments(config: CommandAConfig):
    """The stack in order: a period's window layers (a ring each), then its
    full layer (a layer of the page pool), once a period. A kind's layers
    lie stacked under ``params[kind]`` in the stack's order; the segments
    take them in turn."""
    n = config.layer_switch - 1
    return tuple(seg for _ in range(config.periods) for seg in (
        Segment("window", n, ("ring",)), Segment("full", 1, ("pages",))))


def pool_layout(config: CommandAConfig):
    """(layers, heads, head size) of the page pool: the full layers."""
    return (config.periods, config.num_key_value_heads, config.head_dim)


def state_shapes(config: CommandAConfig) -> Dict[str, tuple]:
    """What a sequence keeps in its row beside the pages: a ring of
    ``ring_tokens`` keys (after rope) and values a window layer, cut into
    pages of ``ring_page`` tokens with the heads outside the page, so that
    the paged kernel reads it as it reads the pool."""
    c = config
    page = (c.ring_tokens // c.ring_page, c.num_key_value_heads,
            c.ring_page, c.head_dim)
    n = c.periods * (c.layer_switch - 1)
    return {"ring_k": (page, c.dtype, n), "ring_v": (page, c.dtype, n)}


def loss_fn(*_, **__):
    raise E.UnimplementedError(
        "Command A+ has no train step: the expert kernel's backward is not "
        "written, and no cut of the model that keeps a whole period fits "
        "training on one chip")


def _stack_layer(kind: str, i, config: CommandAConfig):
    """Layer ``i`` of a kind (``i`` may be traced) as its index in the
    whole stack: where its experts lie."""
    n = config.layer_switch
    if kind == "window":
        return (i // (n - 1)) * n + i % (n - 1)
    return i * n + n - 1


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(config: CommandAConfig, key) -> Dict[str, Any]:
    """Parameter pytree: each kind's layers stacked on axis 0 under its name
    (``window``, ``full``), in the stack's order; the held experts' three
    matrices under ``experts`` (``[L, E, F, D]``: ``gate`` and ``up`` lie
    [outputs, inputs], ``down`` [inputs, outputs]); the table ``embed``,
    both embedding and head.

    The table is ``normal(0, 0.02)`` and every matrix ``normal(0, 1 /
    sqrt(inputs))``, so that each product is as large as its input and a
    branch stands to the others as in a trained model; where that would
    leave a branch under what a comparison of logits can see it is drawn
    larger: the queries 2.5 times (a score's deviation is 2.5: attention
    reads about eight keys of thousands, so that rope, the window's bound
    and the keys' positions show, and not one key, whose pick bf16's
    rounding of a score would flip; over dozens of keys its output would be
    the sequence's average for every row, a part the routers meet as a
    bias that follows the seed), and the held experts' ``down`` four times
    (a token takes one held pick on average, 8 of 128 over the 16 held,
    weighed by about an eighth). The norms' gains are one, and the routers
    are balanced over random sequences (``_balanced``) as a router trained
    to an even load would be."""
    c = config
    D, V = c.hidden_size, c.vocab_size
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    F, Ex, L = c.intermediate_size, c.n_routed_experts, c.num_hidden_layers
    shared = c.num_shared_experts * F
    ks = iter(jax.random.split(key, 32))
    f32 = jnp.float32

    def mat(shape, inputs, times=1.0):
        return (jax.random.normal(next(ks), shape, f32) * times
                * inputs ** -0.5).astype(c.dtype)

    def layers(n):
        return {"ln": jnp.ones((n, D), c.dtype),
                "wq": mat((n, D, H * hd), D, 2.5),
                "wk": mat((n, D, KV * hd), D),
                "wv": mat((n, D, KV * hd), D),
                "wo": mat((n, H * hd, D), H * hd),
                "router": mat((n, D, c.n_router_outputs), D),
                "w_gate": mat((n, D, shared), D),
                "w_up": mat((n, D, shared), D),
                "w_down": mat((n, shared, D), shared)}

    def experts(inputs, times=1.0):
        # a layer at a time: the float32 draw of one stack is 3.2 GB
        return jax.lax.map(
            lambda k: (jax.random.normal(k, (Ex, F, D), f32) * times
                       * inputs ** -0.5).astype(c.dtype),
            jax.random.split(next(ks), L))

    P, n = c.periods, c.layer_switch - 1
    params = {
        "embed": (jax.random.normal(next(ks), (V, D), f32) * 0.02).astype(
            c.dtype),
        "ln_f": jnp.ones((D,), c.dtype),
        "window": layers(P * n),
        "full": layers(P),
        "experts": {"gate": experts(D), "up": experts(D),
                    "down": experts(F, 4.0)},
    }
    return _balanced(params, c, next(ks))


# The routers' balance: sequences of random ids, and tokens a sequence (at
# most), run through the stack when the weights are drawn.
_BALANCE_SEQUENCES = 64
_BALANCE_TOKENS = 1024


def _balanced(params, config: CommandAConfig, key):
    """``params`` with each layer's router made even over the inputs that
    layer meets: ``_BALANCE_SEQUENCES`` sequences of random ids run through
    the stack, a layer at a time, the router of a layer balanced before
    its layer runs. The mean of the layer's normed inputs is taken out of
    every column, so that no expert's logit has a mean over the tokens, and
    each column is scaled to the same spread of its logit.

    Drawn at random, a router is uneven: a sequence's rows share a part
    (attention averages the sequence's values), and the experts whose
    logits lean along it, or spread more over it, are picked more often
    than the others; which experts, and how many a decode step reads,
    follow the seed (PERF.md, the Command A+ findings). A trained router
    is held to an even load (an auxiliary loss or a bias on its scores);
    this gives the load's first two moments, and adds no term to the
    model."""
    c = config
    f32 = jnp.float32
    S = min(_BALANCE_TOKENS, c.max_position_embeddings)
    ids = jax.random.randint(key, (_BALANCE_SEQUENCES, S), 0, c.vocab_size)
    x = jnp.take(params["embed"], ids, axis=0)
    tokens = ids.size
    ops = _NoCache()
    taken = {}
    for seg in segments(c):
        first = taken.get(seg.kind, 0)
        taken[seg.kind] = first + seg.count
        for i in range(first, first + seg.count):
            stack = params[seg.kind]
            lp = {**jax.tree.map(lambda a: a[i], stack),
                  "experts": params["experts"], "layer": i}

            def normed(xs):
                return _ln(xs, lp["ln"], c.layer_norm_eps).astype(f32)

            # a sequence at a time: its normed rows in float32 are 16 MiB
            m = jax.lax.map(lambda xs: normed(xs).sum(0), x).sum(0) / tokens
            u = m * jax.lax.rsqrt(jnp.sum(m * m))
            w = lp["router"].astype(f32)
            w = w - u[:, None] * (u @ w)[None]
            sq = jax.lax.map(lambda xs: jnp.sum(jnp.square(normed(xs) @ w),
                                                0), x)
            sd = jnp.sqrt(sq.sum(0) / tokens)     # the means are now 0
            w = (w * (sd.mean() / sd)).astype(stack["router"].dtype)
            params[seg.kind] = {**stack,
                                "router": stack["router"].at[i].set(w)}
            lp["router"] = w
            x = jax.lax.map(lambda xs: stack_block(
                seg.kind, xs[None], lp, c, i, ops)[0][0], x)
    return params


def _head(params, config: CommandAConfig):
    return params["embed"]


def _ln(x, w, eps):
    """LayerNorm without a bias, statistics in float32."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def final_norm(params, x, config: CommandAConfig):
    return _ln(x, params["ln_f"], config.layer_norm_eps)


def head_logits(params, x, config: CommandAConfig):
    """Float32 logits over the tied table, times ``logit_scale``."""
    logits = _head_logits(x, _head(params, config))
    return logits if config.logit_scale == 1 else logits * config.logit_scale


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def _rotary(c: CommandAConfig, positions):
    """cos, sin [.., head_dim / 2] at ``positions`` [..]."""
    d = c.head_dim
    inv = c.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def _rotated(c: CommandAConfig, t, at):
    """Rows ``t`` [T, heads * hd] rotated at positions ``at`` [T]: a long
    prompt's a piece of rows at a time, each written over the rows it was
    made from (the rotation's halves of a 16,384-token prompt's queries
    would be 1 GiB beside them)."""
    def rot(rows, where):
        cos, sin = _rotary(c, where)
        heads = rows.reshape(1, rows.shape[0], -1, c.head_dim)
        return rope_raw(heads, cos, sin, neox=False).reshape(rows.shape)

    T, n = t.shape[0], EXPERT_TOKENS
    if T <= n or T % n:
        return rot(t, at)

    def one(j, t):
        return jax.lax.dynamic_update_slice_in_dim(t, rot(
            jax.lax.dynamic_slice_in_dim(t, j * n, n),
            jax.lax.dynamic_slice_in_dim(at, j * n, n)), j * n, 0)

    return jax.lax.fori_loop(0, T // n, one, t)


def _attention(h, lp, c: CommandAConfig, kind: str, i, ops):
    """Attention's update [B, S, D] of normed rows ``h`` [B, S, D]: a
    window layer's over its ring ``i``, rope on q and k (keys go into the
    ring rotated); the full layer's over pool layer ``i``, no rope."""
    B, S, D = h.shape
    H, KV, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    pos = jnp.arange(S)[None] if ops.prefill \
        else jnp.maximum(ops.lengths - 1, 0)[:, None]

    with jax.named_scope("attn.proj"):
        q = _mm(h, lp["wq"]).reshape(B * S, H * hd)
        k = _mm(h, lp["wk"]).reshape(B * S, KV * hd)
        v = _mm(h, lp["wv"]).reshape(B, S, KV, hd)
        if kind == "window":
            at = jnp.broadcast_to(pos, (B, S)).reshape(B * S)
            q, k = _rotated(c, q, at), _rotated(c, k, at)
        q, k = q.reshape(B, S, H, hd), k.reshape(B, S, KV, hd)
    scale = hd ** -0.5
    if kind == "window":
        a = ops.attend_ring(q, k, v, i, scale=scale, window=c.sliding_window)
    else:
        a = ops.attend_pages(q, k, v, i, scale=scale)
    with jax.named_scope("attn.proj"):
        return _mm(a.reshape(B, S, H * hd).astype(h.dtype), lp["wo"])


def _swiglu(h, lp):
    return _mm(jax.nn.silu(_mm(h, lp["w_gate"])) * _mm(h, lp["w_up"]),
               lp["w_down"])


def stack_block(kind: str, x, lp, config: CommandAConfig, i, ops):
    """Layer ``i`` of kind ``kind`` on ``x`` [B, S, D] round the program's
    side of the cache, ``ops`` (``inference/paged.py::_StackOps``): its
    ring ``i`` (a window layer) or pool layer ``i`` (a full layer). Hands
    back the picks, [B, S, k], beside the stream. Attention is traced under
    ``attn.window`` / ``attn.full``, the shared experts under ``mlp`` and
    the routed part under ``moe.*``."""
    c = config
    B, S, D = x.shape
    with jax.named_scope("attn.proj"):
        h = _ln(x, lp["ln"], c.layer_norm_eps)
    with jax.named_scope("attn." + kind):
        a = _attention(h, lp, c, kind, i, ops)
    y, e = routed(h.reshape(B * S, D), lp, c,
                  "moe_expert_mlp_decode" if S == 1
                  else "moe_expert_mlp_prefill", _stack_layer(kind, i, c))

    def residual(xr, ar, yr, hr):
        with jax.named_scope("mlp"):
            shared = _swiglu(hr, lp)
        f32 = jnp.float32
        with jax.named_scope("moe.combine"):
            moe = 0.5 * (yr.astype(f32) + shared.astype(f32))
            return (xr.astype(f32) + ar.astype(f32) + moe).astype(xr.dtype)

    # (a long prompt a piece of its rows at a time: the shared experts'
    # intermediates at four experts' width would be 1.5 GiB beside a full
    # chip)
    x = pieces(residual, EXPERT_TOKENS, *(t.reshape(B * S, D) for t in (
        x, a, y, h))).reshape(B, S, D)
    return x, e.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# whole sequences, no cache
# ---------------------------------------------------------------------------

class _NoCache:
    """``stack_block``'s ``ops`` for whole sequences without a cache: plain
    masked attention."""
    prefill = True

    def _attend(self, q, k, v, scale, window=None):
        S = q.shape[1]
        d = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        mask = (d >= 0) if window is None else (d >= 0) & (d < window)
        return sdpa_reference(q, k, v, mask[None, None], scale=scale)

    def attend_ring(self, q, k, v, layer, *, scale, window):
        return self._attend(q, k, v, scale, window)

    def attend_pages(self, q, k, v, layer, *, scale):
        return self._attend(q, k, v, scale)


def forward(params, ids, config: CommandAConfig, with_routes: bool = False):
    """Logits [B, S, V] of whole sequences [B, S] through the same blocks
    the paged programs run (and, asked for, every layer's picks [L, B, S,
    k], in the stack's order)."""
    c = config
    ops = _NoCache()
    x = jnp.take(params["embed"], ids, axis=0)
    taken, routes = {}, []
    for seg in segments(c):
        first = taken.get(seg.kind, 0)
        taken[seg.kind] = first + seg.count
        for i in range(first, first + seg.count):
            lp = {**jax.tree.map(lambda a: a[i], params[seg.kind]),
                  "experts": params["experts"], "layer": i}
            x, e = stack_block(seg.kind, x, lp, c, i, ops)
            routes.append(e)
    logits = head_logits(params, final_norm(params, x, c), c)
    return (logits, jnp.stack(routes)) if with_routes else logits
