"""Phi-4-mini-flash: a decoder-hybrid-decoder stack (SambaY, arXiv:
2507.06607; ``model_type`` ``phi4flash``) of four kinds of layer.

The self-decoder is layers 0..17: Mamba-1 (S6) layers and window
attention alternate, layer 16 is a Mamba layer that also hands on its
memory ``m`` (the scan's output before the gate), layer 17 attends over
the whole context and its keys and values are the only ones the
cross-decoder ever reads. The cross-decoder is layers 18..31: Gated
Memory Units (``(m * silu(h W1)) W2``) and cross-attention (a query
projection alone, layer 17's keys and values) alternate. Every block is
``x += mixer(LN1(x)); x += SwiGLU(LN2(x))`` with LayerNorm (gain and
bias); there is no positional encoding anywhere; the head is the
embedding. Attention is differential: heads of 64 pair up, both halves
of a pair read the pair's values ``[v1 | v2]``, and the pair's output is
``(1 - l0) * RMSNorm_128(a1 - l * a2)`` (the benchmark's plain reference,
``benchmark/architectures/phi4flash.py``, has the equations in full).

Serving only. ``segments`` is the ONE declaration of the stack that the
paged programs (a scan a segment) and the cache (how many layers keep
pages, a ring row, a state row) both read, ``inference/paged.py``:

    mamba_window x 8   a Mamba layer, then a window-attention layer
    mamba_mem    x 1   the Mamba layer that hands on ``m``
    full         x 1   full attention: the one layer of the page pool
    gmu_cross    x 7   a GMU, then a cross-attention layer (prefill
                       runs these on each prompt's last position alone)

What a sequence keeps: pages of ONE pool layer (a key pair ``k1|k2`` and
a value pair ``v1|v2`` are stored as one head of 128, so the paged kernel
serves heads of 64 with no 64-lane page: a query pair goes in as ``q1|0``
and ``0|q2``); for each window layer a ring of ``sliding_window +
ring_page`` tokens (a token at position p lands at ``p mod ring``;
without positions the order inside the ring does not matter, only which
slots are valid); for each Mamba layer a float32 state ``[d_state,
d_inner]`` and the convolution's last ``d_conv - 1`` inputs.

``forward`` is the whole model over whole sequences with no cache (every
layer at every position): what the paged programs are held equal to.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..core import enforce as E
from .llama import _head_logits, _mm
from .stack import Segment

__all__ = ["Phi4FlashConfig", "phi4flash_tiny", "init_params", "forward",
           "segments", "pool_layout", "state_shapes", "stack_block",
           "lambda_init"]


@dataclasses.dataclass
class Phi4FlashConfig:
    """The source's key names; ``mamba_*`` and ``ring_page`` are the
    family's convention (the source's config does not state them)."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    sliding_window: int = 512
    mb_per_layer: int = 2
    tie_word_embeddings: bool = True
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0        # 0: ceil(hidden_size / 16)
    ring_page: int = 16           # tokens a ring holds past the window
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if not self.mamba_dt_rank:
            self.mamba_dt_rank = -(-self.hidden_size // 16)
        L = self.num_hidden_layers
        E.enforce(self.mb_per_layer == 2 and L % 4 == 0 and L >= 8,
                  "the stack written here alternates two kinds of layer "
                  "in each half (mb_per_layer 2) and has a whole number of "
                  "pairs either side of its middle",
                  error=E.UnimplementedError)
        E.enforce(self.tie_word_embeddings,
                  "the head is the embedding (tie_word_embeddings)",
                  error=E.UnimplementedError)
        E.enforce(self.num_attention_heads
                  == 2 * self.num_key_value_heads
                  and self.num_key_value_heads % 2 == 0,
                  "differential attention pairs adjacent heads, and query "
                  "pair j reads key/value pair j // 2")
        E.enforce(self.hidden_size % self.num_attention_heads == 0
                  and self.sliding_window % self.ring_page == 0,
                  "heads divide the hidden size; ring pages the window")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def ring_tokens(self) -> int:
        return self.sliding_window + self.ring_page


def phi4flash_tiny(**kw) -> Phi4FlashConfig:
    """8 layers for tests: 2 x [Mamba, window], Mamba-mem, full, 1 x
    [GMU, cross]; a window of 8, ring pages of 4, heads of 8."""
    base = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                num_hidden_layers=8, num_attention_heads=8,
                num_key_value_heads=4, max_position_embeddings=256,
                sliding_window=8, mamba_d_state=8, ring_page=4,
                dtype=jnp.float32)
    base.update(kw)
    return Phi4FlashConfig(**base)


# ---------------------------------------------------------------------------
# the one declaration of the stack
# ---------------------------------------------------------------------------

def segments(config: Phi4FlashConfig):
    """The stack in order: ``(kind, count, what each layer of the kind
    keeps, whether prefill runs it on the last position alone)``;
    ``params[kind]`` holds the kind's layers stacked for a scan of their
    own."""
    half = config.num_hidden_layers // 2
    return (Segment("mamba_window", half // 2, ("state", "ring")),
            Segment("mamba_mem", 1, ("state",)),
            Segment("full", 1, ("pages",)),
            Segment("gmu_cross", half // 2 - 1, (), last_only=True))


def _layers_keeping(config, what: str) -> int:
    return sum(s.count for s in segments(config) if what in s.keeps)


def pool_layout(config: Phi4FlashConfig):
    """(layers, heads, head size) of the page pool: the layers that keep
    pages, key and value PAIRS as heads of twice the size."""
    return (_layers_keeping(config, "pages"),
            config.num_key_value_heads // 2, 2 * config.head_dim)


def state_shapes(config: Phi4FlashConfig) -> Dict[str, tuple]:
    """What a sequence keeps in its row beside the pages: leaf name ->
    (shape a layer, type, layers). The Mamba state is float32 whatever
    the model's type, ``[d_state, d_inner]`` with the channels on the
    lanes; a ring is ``ring_tokens`` of key (value) pairs cut into pages
    of ``ring_page`` tokens with the heads outside the page, so that the
    paged kernel reads it as it reads the pool."""
    c = config
    mamba, ring = (_layers_keeping(c, k) for k in ("state", "ring"))
    kvp, hd2 = pool_layout(c)[1:]
    page = (c.ring_tokens // c.ring_page, kvp, c.ring_page, hd2)
    return {"ssm": ((c.mamba_d_state, c.d_inner), jnp.float32, mamba),
            "conv": ((c.mamba_d_conv - 1, c.d_inner), c.dtype, mamba),
            "ring_k": (page, c.dtype, ring),
            "ring_v": (page, c.dtype, ring)}


def lambda_init(layer):
    """The differential attention's ``lambda_init`` of (0-based) layer
    ``layer`` of the whole stack (a number or a traced index)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def _layer_index(kind: str, i, config: Phi4FlashConfig):
    """The attention layer's index in the whole stack, for lambda_init
    (``i`` is the layer's index in its segment, traced)."""
    half = config.num_hidden_layers // 2
    if kind == "mamba_window":
        return 2 * i + 1
    if kind == "full":
        return half + 1
    return half + 3 + 2 * i            # gmu_cross: the cross layer


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def init_params(config: Phi4FlashConfig, key) -> Dict[str, Any]:
    """Parameter pytree, each segment's layers stacked on axis 0 under
    the segment's name.

    Matrices are ``normal(0, 0.02)`` but those that would leave a branch
    below what a comparison of logits can see at the published widths:
    the query product (0.04, as PR 27's: with 0.02 a score's deviation is
    1 and each attention is near the mean of its keys, which a wrong
    window or a missing lambda hardly changes; at 0.04 it is 2; at 0.06 for
    queries and keys alike it is 9, attention picks one key, and bf16's
    rounding of a score flips the pick: the check then reads 16% where it
    reads 6% here, PERF.md section 6, PR 31), the GMU's gate (0.04) and the
    Mamba layer's ``x_proj`` / ``dt_proj`` (``normal(0, 1/sqrt(fan_in))``,
    as the Mamba recipe has them: B, C and the step are O(1)). The mixer's
    own parameters follow the recipe: convolution taps ``normal(0,
    1/sqrt(d_conv))``, ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1], ``A = -(1..d_state)`` a channel, ``D``
    ones. The lambda vectors are ``normal(0, 0.2)`` (lambda then stands off
    ``lambda_init`` by some tenths at heads of 64), norms' gains ones and
    their biases ``normal(0, 0.02)`` (a zero bias would hide a dropped
    one)."""
    c = config
    D, Ff, V = c.hidden_size, c.intermediate_size, c.vocab_size
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    di, N, K, R = c.d_inner, c.mamba_d_state, c.mamba_d_conv, c.mamba_dt_rank
    counter = iter(range(10_000))

    def nrm(shape, std=0.02, dtype=None):
        k = jax.random.fold_in(key, next(counter))
        return (jax.random.normal(k, shape, jnp.float32) * std
                ).astype(dtype or c.dtype)

    def block(n):
        """What every layer has round its mixer."""
        return {"ln1_g": jnp.ones((n, D), c.dtype), "ln1_b": nrm((n, D)),
                "ln2_g": jnp.ones((n, D), c.dtype), "ln2_b": nrm((n, D)),
                "gate_up": nrm((n, D, 2 * Ff)), "down": nrm((n, Ff, D))}

    def mamba(n):
        k = jax.random.fold_in(key, next(counter))
        dt = jnp.exp(jax.random.uniform(k, (n, di), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {**block(n),
                "in_proj": nrm((n, D, 2 * di)),
                "conv_w": nrm((n, K, di), K ** -0.5),
                "conv_b": nrm((n, di)),
                "x_proj": nrm((n, di, R + 2 * N), di ** -0.5),
                "dt_proj": nrm((n, R, di), R ** -0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[None, :, None],
                    (n, N, di)),
                "D": jnp.ones((n, di), jnp.float32),
                "out_proj": nrm((n, di, D))}

    def attn(n, cross=False):
        wq = nrm((n, D, nh * hd), 0.04)
        proj = {"wq": wq, "bq": nrm((n, nh * hd))} if cross else {
            "wqkv": jnp.concatenate(
                [wq, nrm((n, D, nkv * hd)), nrm((n, D, nkv * hd))],
                axis=-1),
            "bqkv": nrm((n, (nh + 2 * nkv) * hd))}
        return {**block(n), **proj,
                "wo": nrm((n, nh * hd, D)), "bo": nrm((n, D)),
                "lam": nrm((n, 4, hd), 0.2, jnp.float32),
                "subln": jnp.ones((n, 2 * hd), c.dtype)}

    def gmu(n):
        return {**block(n), "w1": nrm((n, D, di), 0.04),
                "w2": nrm((n, di, D))}

    segs = {s.kind: s.count for s in segments(c)}
    return {
        "embed": nrm((V, D)),
        "mamba_window": {"mamba": mamba(segs["mamba_window"]),
                         "attn": attn(segs["mamba_window"])},
        "mamba_mem": {"mamba": mamba(1)},
        "full": {"attn": attn(1)},
        "gmu_cross": {"gmu": gmu(segs["gmu_cross"]),
                      "attn": attn(segs["gmu_cross"], cross=True)},
        "ln_f_g": jnp.ones((D,), c.dtype), "ln_f_b": nrm((D,)),
    }


def _head(params, config: Phi4FlashConfig):
    return params["embed"]


def final_norm(params, x, config: Phi4FlashConfig):
    return _ln(x, params["ln_f_g"], params["ln_f_b"], config.layer_norm_eps)


# ---------------------------------------------------------------------------
# the pieces of a block
# ---------------------------------------------------------------------------

def _ln(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("mlp")
def _mlp(x, lp, c: Phi4FlashConfig):
    """ln2 + SwiGLU through the fused ``gate_up`` + residual."""
    h = _ln(x, lp["ln2_g"], lp["ln2_b"], c.layer_norm_eps)
    gu = _mm(h, lp["gate_up"])
    f = gu.shape[-1] // 2
    return x + _mm(jax.nn.silu(gu[..., :f]) * gu[..., f:], lp["down"])


# -- differential attention --------------------------------------------------

def _pair_queries(q, c: Phi4FlashConfig):
    """[.., heads * 64] -> [.., heads, 128]: even heads ``q1 | 0``, odd
    heads ``0 | q2``, so that against a stored key pair ``k1 | k2`` each
    reads its own half."""
    lead, hd = q.shape[:-1], c.head_dim
    q = q.reshape(*lead, c.num_attention_heads // 2, 2, hd)
    z = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], z], -1),
                      jnp.concatenate([z, q[..., 1, :]], -1)],
                     axis=-2).reshape(*lead, c.num_attention_heads, 2 * hd)


def _pairs(t, c: Phi4FlashConfig):
    """Keys or values [.., kv_heads * 64] as pairs [.., kv_heads/2, 128]."""
    return t.reshape(*t.shape[:-1], c.num_key_value_heads // 2,
                     2 * c.head_dim)


def _combine(a, lp, layer, c: Phi4FlashConfig):
    """``a`` [.., heads, 128] (head 2j is a1 of pair j, head 2j+1 its a2)
    -> [.., hidden]: ``(1 - l0) * RMSNorm_128(a1 - l * a2)``."""
    f32 = jnp.float32
    lam = lp["lam"].astype(f32)
    l0 = lambda_init(layer)
    l = (jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3]))
         + l0)
    lead = a.shape[:-2]
    a = a.astype(f32).reshape(*lead, c.num_attention_heads // 2, 2, -1)
    d = a[..., 0, :] - l * a[..., 1, :]
    d = d * lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + c.layer_norm_eps)
    d = d * lp["subln"].astype(f32) * (1.0 - l0)
    return d.reshape(*lead, -1)


def _attention(x, lp, kind, i, c: Phi4FlashConfig, attend):
    """A window, full or cross attention layer's mixer round the caller's
    core: ``attend(q [B, S, heads, 128], k, v [B, S, pairs, 128] or None
    for a cross layer) -> a [B, S, heads, 128]``."""
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    with jax.named_scope("attn.proj"):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], c.layer_norm_eps)
        if kind == "gmu_cross":
            q = _mm(h, lp["wq"]) + lp["bq"]
            k = v = None
        else:
            qkv = _mm(h, lp["wqkv"]) + lp["bqkv"]
            q = qkv[..., :nh * hd]
            k = _pairs(qkv[..., nh * hd:(nh + nkv) * hd], c)
            v = _pairs(qkv[..., (nh + nkv) * hd:], c)
        q = _pair_queries(q, c)
    a = attend(q, k, v)
    with jax.named_scope("attn.proj"):
        a = _combine(a, lp, _layer_index(kind, i, c), c).astype(x.dtype)
        return x + _mm(a, lp["wo"]) + lp["bo"]


# -- the Mamba-1 mixer -----------------------------------------------------
# Traced under ``ssm`` and, inside it, ``ssm.proj`` / ``ssm.conv`` /
# ``ssm.scan`` (prefill) / ``ssm.update`` (decode), as models/falcon_h1.py.

@jax.named_scope("ssm.proj")
def _mamba_in(h, lp, c: Phi4FlashConfig):
    xz = _mm(h, lp["in_proj"])
    return xz[..., :c.d_inner], xz[..., c.d_inner:]


@jax.named_scope("ssm.proj")
def _mamba_steps(x, lp, c: Phi4FlashConfig):
    """From the convolution's output: the step [.., d_inner] float32, and
    B, C [.., d_state]."""
    R, N = c.mamba_dt_rank, c.mamba_d_state
    dbc = _mm(x, lp["x_proj"])
    dt = jax.nn.softplus(
        _mm(dbc[..., :R], lp["dt_proj"]).astype(jnp.float32) + lp["dt_bias"])
    return dt, dbc[..., R:R + N], dbc[..., R + N:]


@jax.named_scope("ssm.proj")
def _mamba_out(y, x, z, lp):
    """``y`` float32 without the skip -> (the layer's output, the memory
    ``m = y + D x`` in the model's type)."""
    m = (y + lp["D"] * x.astype(jnp.float32)).astype(z.dtype)
    return _mm(m * jax.nn.silu(z), lp["out_proj"]), m


def _a_of(lp):
    return -jnp.exp(lp["A_log"].astype(jnp.float32))          # [N, d_inner]


@jax.named_scope("ssm")
def mamba_prefill(h, lp, config: Phi4FlashConfig, slen):
    """Whole sequences ``h`` [G, S, D], row g valid up to ``slen[g]``:
    (out [G, S, D], m [G, S, d_inner], the state each row is in after
    ``slen[g]`` tokens). A padded token takes a zero step, so it neither
    decays the state nor adds to it."""
    from ..kernels import dispatched_s6_scan

    c = config
    S, K = h.shape[1], c.mamba_d_conv
    x, z = _mamba_in(h, lp, c)
    with jax.named_scope("ssm.conv"):
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(xp[:, j:j + S] * lp["conv_w"][j] for j in range(K))
        x = jax.nn.silu(conv + lp["conv_b"])
        tail = jnp.take_along_axis(
            xp, (slen[:, None] + jnp.arange(K - 1))[:, :, None], axis=1)
    dt, b, cc = _mamba_steps(x, lp, c)
    dt = jnp.where((jnp.arange(S) < slen[:, None])[..., None], dt, 0.0)
    y, last = dispatched_s6_scan(x, dt, _a_of(lp), b, cc)
    out, m = _mamba_out(y, x, z, lp)
    return out, m, {"ssm": last, "conv": tail}


@jax.named_scope("ssm")
def mamba_decode(h, lp, config: Phi4FlashConfig, state, layer, rows):
    """One token a slot: ``h`` [B, 1, D] against the state leaves ``ssm``
    and ``conv`` ([layers, rows, ...]), slot i's row ``rows[i]``. Returns
    (out [B, 1, D], m [B, 1, d_inner], the two leaves with layer
    ``layer``'s rows updated in place)."""
    from ..kernels import dispatched_s6_update

    c = config
    x, z = _mamba_in(h, lp, c)
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate(
            [state["conv"][layer, rows].astype(x.dtype), x], axis=1)
        conv = jnp.einsum("bkc,kc->bc", window, lp["conv_w"])
        x = jax.nn.silu(conv + lp["conv_b"])                     # [B, di]
        tails = state["conv"].at[layer, rows].set(
            window[:, 1:].astype(state["conv"].dtype))
    dt, b, cc = _mamba_steps(x, lp, c)
    with jax.named_scope("ssm.update"):
        ssm, y = dispatched_s6_update(
            state["ssm"], layer, rows, dt, dt * x.astype(jnp.float32),
            _a_of(lp), b, cc)
    out, m = _mamba_out(y[:, None], x[:, None], z, lp)
    return out, m, {"ssm": ssm, "conv": tails}


def _gmu(x, m, lp, c: Phi4FlashConfig):
    with jax.named_scope("gmu"):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], c.layer_norm_eps)
        return x + _mm(m * jax.nn.silu(_mm(h, lp["w1"])), lp["w2"])


# ---------------------------------------------------------------------------
# a block of each kind, round the program's cache (inference/paged.py)
# ---------------------------------------------------------------------------

def _mamba_layer(x, lp, c, ops, state_layer):
    with jax.named_scope("ssm"), jax.named_scope("ssm.proj"):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"], c.layer_norm_eps)
    if ops.prefill:
        out, m, st = mamba_prefill(h, lp, c, ops.slen)
        ops.write_state(state_layer, st)
    else:
        out, m, st = mamba_decode(h, lp, c, ops.state, state_layer, ops.rows)
        ops.state = {**ops.state, **st}
    return _mlp(x + out, lp, c), m


def stack_block(kind: str, x, lp, config: Phi4FlashConfig, i, ops):
    """Layer(s) ``i`` of segment ``kind`` on ``x`` [B, S, D] round the
    program's side of the cache, ``ops`` (``inference/paged.py::
    _StackOps``: the pages, the rings, the state rows, and ``ops.shared``,
    what one segment hands to the later ones: here ``m``)."""
    c = config
    scale = c.head_dim ** -0.5
    pairs = segments(c)[0].count
    if kind in ("mamba_window", "mamba_mem"):
        x, m = _mamba_layer(x, lp["mamba"], c, ops,
                            i if kind == "mamba_window" else pairs)
        if kind == "mamba_mem":
            ops.shared = m
            return x
        with jax.named_scope("attn.window"):
            x = _attention(
                x, lp["attn"], kind, i, c,
                lambda q, k, v: ops.attend_ring(
                    q, k, v, i, scale=scale, window=c.sliding_window))
        return _mlp(x, lp["attn"], c)
    if kind == "gmu_cross":
        x = _mlp(_gmu(x, ops.shared, lp["gmu"], c), lp["gmu"], c)
    with jax.named_scope("attn.shared"):
        x = _attention(x, lp["attn"], kind, i, c,
                       lambda q, k, v: ops.attend_pages(q, k, v, 0,
                                                        scale=scale))
    return _mlp(x, lp["attn"], c)


# ---------------------------------------------------------------------------
# whole sequences, no cache: every layer at every position
# ---------------------------------------------------------------------------

class _NoCache:
    """``stack_block``'s ``ops`` for whole sequences without a cache:
    plain masked attention, keys and values of the full layer kept for
    the cross layers, states dropped."""
    prefill = True

    def __init__(self, slen):
        self.slen, self.shared, self._kv = slen, None, None

    def write_state(self, layer, st):
        pass

    def _attend(self, q, k, v, scale, window=None):
        from ..nn.functional.attention import sdpa_reference

        S = q.shape[1]
        d = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
        mask = (d >= 0) if window is None else (d >= 0) & (d < window)
        return sdpa_reference(q, k, v, mask[None, None], scale=scale)

    def attend_ring(self, q, k, v, layer, *, scale, window):
        return self._attend(q, k, v, scale, window)

    def attend_pages(self, q, k, v, layer, *, scale):
        if k is not None:
            self._kv = (k, v)
        return self._attend(q, *self._kv, scale)


def forward(params, ids, config: Phi4FlashConfig):
    """Logits [B, S, V] of whole sequences [B, S]: the same blocks as the
    paged programs run, the cross-decoder over every position."""
    c = config
    B, S = ids.shape
    ops = _NoCache(jnp.full((B,), S, jnp.int32))
    x = jnp.take(params["embed"], ids, axis=0)
    for seg in segments(c):
        for i in range(seg.count):
            lp = jax.tree.map(lambda a: a[i], params[seg.kind])
            x = stack_block(seg.kind, x, lp, c, i, ops)
    return _head_logits(final_norm(params, x, c), _head(params, c))
