"""ZAYA1 (Zyphra, ``model_type`` ``zaya``): attention inside a compressed
latent with two causal convolutions over queries and keys (CCA,
arXiv:2510.04476), then sixteen experts of which a token takes ONE,
picked by an MLP router that carries its input from layer to layer
(arXiv:2511.17127). The benchmark's plain reference
(``benchmark/architectures/zaya.py``) has the equations in full.

One layer, on ``h = RMSNorm(x)`` before each sublayer:

- attention: ``u = [W_Q h ; W_K h]`` (8 + 2 heads of 128); a depthwise and
  a grouped convolution of two taps each along the sequence; the mean of
  the unconvolved pair added back; queries and keys normed to ``sqrt(d)``
  (keys times a learned temperature a head), rotary on half of a head;
  key head 0's value is ``W_V1 h_t``, key head 1's ``W_V2 h_{t-1}``;
  grouped-query attention in the latent, ``W_O`` back out.
- experts: ``r_l = W_d h + gamma_l r_{l-1}`` (256 wide, float32), ``s =
  softmax(MLP(RMSNorm(r_l)))`` over 16, ``e = argmax(s + b)``, ``y = s_e
  expert_e(h)``: no shared expert, no capacity, nothing dropped
  (``kernels/moe_experts.py``).
- each sublayer lands as ``x <- alpha x + beta y``, two learned vectors.

Serving only. What a sequence keeps beside its keys and values is the
tail of the two convolutions and the value that belongs to the next
token: ``u_{t-1}``, ``a_{t-1}`` and ``W_V2 h_{t-1}``, one row of 2,688
numbers a layer (``state_shapes``), written by a prefill as its last
position leaves them and read and rewritten in place by every decode
step. The paged programs (``inference/paged.py``) compose the layer
through ``paged_block``; the layer scan carries ``(x, r)``, the residual
stream and the router's input of the layer before (``stream``), and hands
back which expert every token took. The experts' weights are kept out of
the scanned ``params["layers"]`` (``params["experts"]``, 8 GB at 20
layers): the kernel reads an expert's matrices where they lie, by layer.

Shares ``_mm``, ``_rms``, ``_head_logits`` and the rope helpers with
``models/llama.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core import enforce as E
from ..nn.functional.attention import rope_raw, rope_tables, sdpa_raw
from .llama import _head_logits, _mm, _rms



__all__ = ["ZayaConfig", "zaya_tiny", "init_params", "forward",
           "paged_block", "mixer_prefill", "mixer_decode", "state_shapes",
           "stream", "expert_sublayer", "router_scores"]


@dataclasses.dataclass
class ZayaConfig:
    """The source's key names. ``rope_parameters`` is the source's nested
    group: the layers here are all its ``"hybrid"``."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    partial_rotary_factor: float = 0.5
    rope_parameters: Optional[dict] = None
    layer_types: Optional[Tuple[str, ...]] = None
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = tuple(self.layer_types or ())[:self.num_hidden_layers]
        self.layer_types = tuple(self.layer_types) if self.layer_types \
            else None
        E.enforce(all(k == "hybrid" for k in kinds)
                  and self.cca_time0 == 2 and self.cca_time1 == 2
                  and self.num_experts_per_tok == 1
                  and self.tie_word_embeddings,
                  "only the published ZAYA1-8B layer is written: every "
                  "layer 'hybrid' (no sliding window), two taps a "
                  "convolution, one expert a token, a tied table",
                  error=E.UnimplementedError)
        E.enforce(self.num_attention_heads % self.num_key_value_heads == 0,
                  "query heads must be a multiple of key-value heads")

    @property
    def rope_theta(self) -> float:
        rp = (self.rope_parameters or {}).get("hybrid", {})
        return float(rp.get("rope_theta", 5e6))

    @property
    def rotary_dim(self) -> int:
        """The part of a head that rotates: the first ``head_dim *
        partial_rotary_factor`` of it."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def latent(self) -> int:
        """Channels the convolutions run over: query and key heads."""
        return (self.num_attention_heads
                + self.num_key_value_heads) * self.head_dim


def zaya_tiny(**kw) -> ZayaConfig:
    """Small config for tests: two key heads (one takes the shifted
    value), queries a multiple of them, experts fewer than a step's rows."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_experts=4, moe_intermediate_size=32,
                router_hidden_size=16, max_position_embeddings=256,
                rope_parameters={"hybrid": {"rope_theta": 10000.0}},
                dtype=jnp.float32)
    base.update(kw)
    return ZayaConfig(**base)


def init_params(config: ZayaConfig, key) -> Dict[str, Any]:
    """Parameter pytree: per-layer weights stacked on axis 0 under
    ``layers``, the experts' three matrices under ``experts`` (``[L, E,
    F, D]`` each: ``gate`` and ``up`` lie [outputs, inputs], ``down``
    [inputs, outputs]).

    The table is ``normal(0, 0.02)`` as the other families' and every
    matrix ``normal(0, 1 / sqrt(inputs))``, which at the published widths
    is 0.022 and at a test's keeps each product as large as its input, so
    that a tiny model's branches stand to each other as the real one's do
    (the gated product is quadratic in the scale: at 0.02 and 64 inputs
    the experts would be a fortieth of attention). What a plain draw would
    leave invisible or a coin toss is drawn so that it is neither:
    ``down`` four times that (the pick's probability, a fifth on average,
    scales an expert's output); convolution taps ``normal(0, 1/sqrt(2))``
    a channel and a grouped entry ``normal(0, 1/sqrt(2 x head))`` (a
    convolution's output is as large as its input, and as the mean added
    back), their biases ``normal(0, 0.1)``; the keys' temperature uniform
    in [1, 2] (scores of deviation 1-2: attention picks few tokens, not
    one and not their mean); ``gamma`` uniform in [0.3, 0.7]; the router's
    last matrix ``normal(0, 2.5 / sqrt(inputs))``, each expert's column
    centred (its 16 logits spread by about 0.9: at twice that, what bf16
    does to the stream moved a pick's probability by up to 0.2 and one
    logit in 2.6 M by a fifth of the largest, PERF.md section 6, PR 33),
    the balancing bias made by balancing (``_balancing_bias``: about 0.05
    in size, it moves a pick in three and weighs nothing); ``alpha`` 1 +-
    0.2 and ``beta`` 1 +- 0.25 a channel."""
    c = config
    L, D, V = c.num_hidden_layers, c.hidden_size, c.vocab_size
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    Ex, F, R, C = (c.num_experts, c.moe_intermediate_size,
                   c.router_hidden_size, c.latent)
    ks = jax.random.split(key, 24)
    f32 = jnp.float32

    def nrm(k, shape, std, mean=0.0):
        return (mean + jax.random.normal(k, shape, f32) * std).astype(c.dtype)

    def mat(k, shape, inputs, times=1.0):
        return nrm(k, shape, times * inputs ** -0.5)

    def uni(k, shape, lo, hi):
        return jax.random.uniform(k, shape, f32, lo, hi).astype(c.dtype)

    def centred(w):
        # each expert's column sums to zero over the router's units: a
        # GeLU's outputs have a mean, and an uncentred column turns it
        # into a constant logit that favours the same experts on every
        # token (11 of 16 read by a step's 64 rows, for 15 when centred)
        wf = w.astype(f32)
        return (wf - wf.mean(-2, keepdims=True)).astype(c.dtype)

    def experts(k, inputs, times=1.0):
        # a layer at a time: the float32 draw of one stack is 5 GB
        return lax.map(lambda kl: mat(kl, (Ex, F, D), inputs, times),
                       jax.random.split(k, L))

    w1, w2 = mat(ks[15], (L, R, R), R), mat(ks[16], (L, R, R), R)
    w3 = centred(mat(ks[17], (L, R, Ex), R, 2.5))
    return {
        "embed": nrm(ks[0], (V, D), 0.02),
        "layers": {
            "ln1": jnp.ones((L, D), c.dtype),
            "wq": mat(ks[1], (L, D, nh * hd), D),
            "wk": mat(ks[2], (L, D, nkv * hd), D),
            "wv1": mat(ks[3], (L, D, hd), D),
            "wv2": mat(ks[4], (L, D, hd), D),
            "wo": mat(ks[5], (L, nh * hd, D), nh * hd),
            "conv1_w": mat(ks[6], (L, 2, C), 2),
            "conv1_b": nrm(ks[7], (L, C), 0.1),
            "conv2_w": mat(ks[8], (L, 2, nh + nkv, hd, hd), 2 * hd),
            "conv2_b": nrm(ks[9], (L, C), 0.1),
            "tau": uni(ks[10], (L, nkv), 1.0, 2.0),
            "a_alpha": nrm(ks[11], (L, D), 0.2, 1.0),
            "a_beta": nrm(ks[12], (L, D), 0.25, 1.0),
            "ln2": jnp.ones((L, D), c.dtype),
            "wd": mat(ks[13], (L, D, R), D),
            "gamma": uni(ks[14], (L, R), 0.3, 0.7),
            "rnorm": jnp.ones((L, R), c.dtype),
            "w1": w1, "w2": w2, "w3": w3,
            "rbias": jax.vmap(_balancing_bias)(
                jax.random.split(ks[18], L), w1, w2, w3).astype(c.dtype),
            "m_alpha": nrm(ks[19], (L, D), 0.2, 1.0),
            "m_beta": nrm(ks[20], (L, D), 0.25, 1.0),
        },
        "experts": {"gate": experts(ks[21], D), "up": experts(ks[22], D),
                    "down": experts(ks[23], F, 4.0)},
        "ln_f": jnp.ones((D,), c.dtype),
    }


def _router_mlp(z, w1, w2, w3):
    """The router's three layers on normed inputs ``z``: softmax over
    the experts, float32."""
    f32 = jnp.float32
    for w in (w1, w2):
        z = jax.nn.gelu(z @ w.astype(f32), approximate=False)
    return jax.nn.softmax(z @ w3.astype(f32), -1)


def _balancing_bias(key, w1, w2, w3, samples=4096, rounds=200):
    """One layer's selection bias, made as the published one is: moved
    against each expert's load until the picks are even (here on
    ``samples`` normed inputs drawn normal, since there is no training
    run to take the load from). With random router weights and no bias
    a quarter of the tokens take one expert and a step's 64 rows reach
    12 of 16; a trained model's bias is what keeps that from happening.
    It enters the pick alone, never the weight."""
    s = _router_mlp(jax.random.normal(key, (samples, w1.shape[0])), w1, w2,
                    w3)
    n = s.shape[-1]

    def step(b, _):
        load = jnp.mean(jax.nn.one_hot(jnp.argmax(s + b, -1), n), 0)
        return b - 0.02 * (load - 1.0 / n), None

    return lax.scan(step, jnp.zeros((n,)), None, length=rounds)[0]


# ---------------------------------------------------------------------------
# the pieces the paged programs reach by name
# ---------------------------------------------------------------------------

def _head(params, config: ZayaConfig):
    return params["embed"]


def state_shapes(config: ZayaConfig) -> Dict[str, tuple]:
    """What a sequence keeps beside its keys and values, a layer: one row
    ``[u_{t-1} | a_{t-1} | W_V2 h_{t-1}]`` in the model's type (the three
    are activations of that type, stored as computed)."""
    return {"cca": ((2 * config.latent + config.head_dim,), config.dtype)}


def stream(x, config: ZayaConfig):
    """What the layer scan carries a token: the residual stream and the
    router's input of the layer before (zeros before the first)."""
    return x, jnp.zeros(x.shape[:-1] + (config.router_hidden_size,),
                        jnp.float32)


# ---------------------------------------------------------------------------
# attention inside the latent
# ---------------------------------------------------------------------------

@jax.named_scope("attn.proj")
def _latent(h, lp):
    """Step 1 and the values' products: u = [W_Q h ; W_K h], W_V1 h,
    W_V2 h."""
    u = jnp.concatenate([_mm(h, lp["wq"]), _mm(h, lp["wk"])], -1)
    return u, _mm(h, lp["wv1"]), _mm(h, lp["wv2"])


def _depthwise(u, u_prev, lp):
    """Step 2's first convolution: ``a_t`` from ``u_t`` and ``u_{t-1}``."""
    w1 = lp["conv1_w"]
    return w1[0] * u_prev + w1[1] * u + lp["conv1_b"]


def _grouped(u, a, a_prev, lp, c: ZayaConfig):
    """Step 2's second convolution (a matrix a head a tap) and step 3,
    the mean of the unconvolved pair added back: (queries [.., heads, d],
    keys [.., kv, d]), unnormed."""
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    w2 = lp["conv2_w"]
    heads = a.shape[:-1] + (nh + nkv, hd)
    cc = (jnp.einsum("...gi,gio->...go", a_prev.reshape(heads), w2[0])
          + jnp.einsum("...gi,gio->...go", a.reshape(heads), w2[1])
          + lp["conv2_b"].reshape(nh + nkv, hd))
    ut = u.reshape(heads)
    qt, kt = ut[..., :nh, :], ut[..., nh:, :]
    g = nh // nkv
    q = cc[..., :nh, :] + (qt + jnp.repeat(kt, g, axis=-2)) * 0.5
    k = cc[..., nh:, :] + (
        qt.reshape(qt.shape[:-2] + (nkv, g, hd)).mean(-2).astype(kt.dtype)
        + kt) * 0.5
    return q, k


def _norm_rotate(q, k, lp, c: ZayaConfig, cos, sin):
    """Step 4: each head to length sqrt(d) (keys times their
    temperature), then rotary on the first ``rotary_dim`` of a head."""
    f32 = jnp.float32

    def unit(t):
        tf = t.astype(f32)
        return tf * lax.rsqrt(jnp.mean(tf * tf, -1, keepdims=True)
                              + c.rms_norm_eps)

    q = unit(q).astype(q.dtype)
    k = (unit(k) * lp["tau"].astype(f32)[:, None]).astype(k.dtype)
    rd = c.rotary_dim

    def rot(t):
        return jnp.concatenate([rope_raw(t[..., :rd], cos, sin),
                                t[..., rd:]], -1)

    return rot(q), rot(k)


@jax.named_scope("attn.cca")
def mixer_prefill(h, lp, config: ZayaConfig, slen):
    """Steps 1-5 (but the norm and rotary, which need the positions) on
    whole sequences ``h`` [G, S, D], row g valid up to ``slen[g]``:
    ((queries, keys, values), the row each sequence keeps: what its
    position ``slen[g]`` - 1 leaves for the next)."""
    c = config
    u, v1, v2 = _latent(h, lp)

    def before(t):                      # t_{s-1}, zeros before the start
        return jnp.pad(t, ((0, 0), (1, 0), (0, 0)))[:, :-1]

    a = _depthwise(u, before(u), lp)
    q, k = _grouped(u, a, before(a), lp, c)
    v = jnp.stack([v1, before(v2)], axis=2)
    last = jnp.maximum(slen - 1, 0)[:, None, None]
    tail = jnp.concatenate([jnp.take_along_axis(t, last, axis=1)[:, 0]
                            for t in (u, a, v2)], -1)
    return (q, k, v), {"cca": tail}


@jax.named_scope("attn.cca")
def mixer_decode(h, lp, config: ZayaConfig, state, layer, rows):
    """One token a slot: ``h`` [B, 1, D] against the slots' rows of
    ``state["cca"]`` [layers, rows, 2 x latent + d] (a slot with nothing to
    keep names the last row, which no sequence owns). Returns ((queries,
    keys, values), the state with layer ``layer``'s rows rewritten in
    place)."""
    c = config
    C = c.latent
    u, v1, v2 = _latent(h, lp)
    tail = state["cca"][layer, rows].astype(u.dtype)[:, None]     # [B, 1, .]
    a = _depthwise(u, tail[..., :C], lp)
    q, k = _grouped(u, a, tail[..., C:2 * C], lp, c)
    v = jnp.stack([v1, tail[..., 2 * C:]], axis=2)
    new = jnp.concatenate([u, a, v2], -1)[:, 0]
    return (q, k, v), {"cca": state["cca"].at[layer, rows].set(
        new.astype(state["cca"].dtype))}


# ---------------------------------------------------------------------------
# the experts
# ---------------------------------------------------------------------------

def router_scores(h, r_prev, lp, c: ZayaConfig):
    """(r_l, s [.., E]): the router's carried input and its softmax, in
    float32 from the projection on."""
    f32 = jnp.float32
    r = _mm(h, lp["wd"]).astype(f32) + lp["gamma"].astype(f32) * r_prev
    z = r * lax.rsqrt(jnp.mean(r * r, -1, keepdims=True) + c.rms_norm_eps) \
        * lp["rnorm"].astype(f32)
    return r, _router_mlp(z, lp["w1"], lp["w2"], lp["w3"])


def expert_sublayer(x, r_prev, lp, c: ZayaConfig, name="moe_expert_mlp"):
    """ln2, the router, the token's one expert, the scaled residual:
    (x', r_l, the expert each token took [.., ] int32). ``lp`` holds the
    experts' stacks whole (``lp["experts"]``) and the layer to read
    (``lp["layer"]``)."""
    from ..kernels import dispatched_expert_mlp

    with jax.named_scope("moe.route"):
        h = _rms(x, lp["ln2"], c.rms_norm_eps)
        r, s = router_scores(h, r_prev, lp, c)
        e = jnp.argmax(s + lp["rbias"].astype(jnp.float32), -1) \
            .astype(jnp.int32)
        w = jnp.take_along_axis(s, e[..., None], -1)
    ex = lp["experts"]
    y = dispatched_expert_mlp(
        h.reshape(-1, h.shape[-1]), e.reshape(-1), ex["gate"], ex["up"],
        ex["down"], lp["layer"], name=name).reshape(h.shape)
    with jax.named_scope("moe.combine"):
        x = lp["m_alpha"] * x + lp["m_beta"] * (w.astype(x.dtype) * y)
    return x, r, e


# ---------------------------------------------------------------------------
# the layer, and whole sequences
# ---------------------------------------------------------------------------

def paged_block(xr, lp, config: ZayaConfig, cos, sin, attend, mix):
    """One layer round the caller's attention core and its way to the
    rows (the seam of ``inference/paged.py``): ``xr`` is the stream ``(x,
    r)``; ``mix(h, lp) -> ((q, k, v), the rows' extra)``; ``attend(q, k,
    v) -> (a, its own extra)``; ``cos`` / ``sin`` over ``rotary_dim``.
    Returns (the stream, attend's extra, mix's, the experts taken)."""
    c = config
    x, r = xr
    with jax.named_scope("attn.proj"):
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
    (q, k, v), st = mix(h, lp)
    with jax.named_scope("attn.cca"):
        q, k = _norm_rotate(q, k, lp, c, cos, sin)
    a, kv = attend(q, k, v)
    with jax.named_scope("attn.proj"):
        x = lp["a_alpha"] * x + lp["a_beta"] * _mm(a.astype(x.dtype),
                                                   lp["wo"])
    x, r, e = expert_sublayer(
        x, r, lp, c, name="moe_expert_mlp_decode" if x.shape[1] == 1
        else "moe_expert_mlp_prefill")
    return (x, r), kv, st, e


def forward(params, ids, config: ZayaConfig, with_routes: bool = False):
    """Logits [B, S, V] of whole sequences [B, S]: the paged prefill's
    layer with plain causal attention and no cache (and, asked for, the
    expert every token took, [L, B, S])."""
    c = config
    B, S = ids.shape
    x = jnp.take(params["embed"], ids, axis=0)
    cos, sin = rope_tables(S, c.rotary_dim, theta=c.rope_theta)
    slen = jnp.full((B,), S, jnp.int32)

    def attend(q, k, v):
        with jax.named_scope("attn.kernel"):
            return sdpa_raw(q, k, v, is_causal=True).reshape(B, S, -1), None

    def step(xr, xs):
        lp = {**xs[0], "experts": params["experts"], "layer": xs[1]}
        xr, _, _, e = paged_block(
            xr, lp, c, cos, sin, attend,
            lambda h, lp: mixer_prefill(h, lp, c, slen))
        return xr, e

    (x, _), routes = lax.scan(
        step, stream(x, c),
        (params["layers"], jnp.arange(c.num_hidden_layers)))
    logits = _head_logits(_rms(x, params["ln_f"], c.rms_norm_eps),
                          params["embed"])
    return (logits, routes) if with_routes else logits
