"""Falcon-H1: a Mamba-2 mixer beside grouped-query attention in every
block (tiiuae/Falcon-H1, ``model_type`` ``falcon_h1``).

One block, on ``h = RMSNorm(x)``: the mixer and the attention read the
SAME ``h`` and land in one residual add, then a SwiGLU feed-forward; muP
multipliers from the config scale each branch (the benchmark's plain
reference, ``benchmark/architectures/falcon_h1.py``, has the equations in
full). The mixer: one projection to ``[z | x B C | dt]``, a causal
depthwise convolution over ``[x B C]``, the selective recurrence
(``kernels/ssm.py``), a gate and a grouped RMSNorm, one projection out.

Serving only. The mixer comes in the two forms serving needs, which a
test holds equal: ``mixer_prefill`` (whole prompts, the chunked scan) and
``mixer_decode`` (one token a slot against the state a slot, updated in
place). The paged programs (``inference/paged.py``) compose the block
through ``paged_block`` and keep the state beside the page pool. There is
no ``loss_fn`` / ``make_train_step`` here: no cut of this model trains on
one chip, and the scan has no backward pass written for it.

Shares ``_mm``, ``_rms``, ``_qkv_proj``, ``_head_logits`` and the rope
helpers with ``models/llama.py``. Per-layer weights are stacked on axis 0
like the other families': every block is alike, so the layer scan is one
scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core import enforce as E
from ..nn.functional.attention import rope_raw, rope_tables, sdpa_raw
from .llama import _head_logits, _mm, _qkv_proj, _rms

__all__ = ["FalconH1Config", "falcon_h1_tiny", "init_params", "forward",
           "decode_mlp", "paged_block", "mixer_prefill", "mixer_decode",
           "state_shapes", "embed_tokens", "head_logits"]


@dataclasses.dataclass
class FalconH1Config:
    """The source's key names. ``head_dim`` is a field: 128 here, where
    ``hidden_size // num_attention_heads`` is 256."""
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    tie_word_embeddings: bool = False
    mamba_d_ssm: int = 4096
    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # on the segments z, x, B, C, dt of the mixer's projection
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        self.mlp_multipliers = tuple(self.mlp_multipliers)
        self.ssm_multipliers = tuple(self.ssm_multipliers)
        self.rope_theta = float(self.rope_theta)   # 1e11 overflows an int32
        E.enforce(self.mamba_d_ssm == self.mamba_n_heads * self.mamba_d_head,
                  "mamba_d_ssm must be mamba_n_heads x mamba_d_head")
        E.enforce(self.mamba_n_heads % self.mamba_n_groups == 0,
                  "mamba_n_heads must be a multiple of mamba_n_groups")
        E.enforce(self.mamba_rms_norm and not self.mamba_norm_before_gate
                  and self.mamba_conv_bias and not self.mamba_proj_bias,
                  "only the published mixer is written: a gated grouped "
                  "RMSNorm after the gate, a convolution bias, no "
                  "projection bias", error=E.UnimplementedError)

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state


def falcon_h1_tiny(**kw) -> FalconH1Config:
    """Small config for tests: two groups, heads a multiple of groups, a
    chunk shorter than a test's prompts, every multiplier off 1."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=8,
                max_position_embeddings=256, rope_theta=10000.0,
                mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
                mamba_n_groups=2, mamba_d_state=8, mamba_chunk_size=8,
                attention_in_multiplier=0.9, attention_out_multiplier=0.5,
                key_multiplier=0.6, embedding_multiplier=2.0,
                lm_head_multiplier=0.25, mlp_multipliers=(0.7, 0.4),
                ssm_in_multiplier=0.8, ssm_out_multiplier=0.6,
                ssm_multipliers=(0.9, 0.8, 0.7, 1.1, 0.6),
                dtype=jnp.float32)
    base.update(kw)
    return FalconH1Config(**base)


def init_params(config: FalconH1Config, key) -> Dict[str, Any]:
    """Parameter pytree, per-layer weights stacked on axis 0.

    With ``normal(0, 0.02)`` on every matrix, as the other families have
    it, the source's multipliers (``key_multiplier`` 0.011,
    ``attention_out_multiplier`` 0.0375, ``lm_head_multiplier`` 0.0078 at
    34B) would shrink whole branches below what a comparison of logits
    can see: the multipliers were fitted to weights of the scale muP
    trains, not to 0.02. So a matrix's deviation here is 0.02 over the
    product of the multipliers that scale its output (or, for the
    embedding, its rows): what each branch adds is then what a plain
    0.02 model's would be, and a wrong or missing multiplier changes the
    logits. The queries' matrix has twice that, so that attention picks
    few tokens and not their mean. The mixer's own parameters follow the
    Mamba-2 recipe: convolution taps ``normal(0, 1/sqrt(d_conv))``,
    ``dt_bias`` the inverse softplus of a step log-uniform in
    [0.001, 0.1], ``A_log`` the log of a uniform in [1, 16], ``D`` ones."""
    c = config
    L, D, Ff, V = (c.num_hidden_layers, c.hidden_size, c.intermediate_size,
                   c.vocab_size)
    nh, nkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    H, ds, K, cd = c.mamba_n_heads, c.mamba_d_ssm, c.mamba_d_conv, c.conv_dim
    gn = c.mamba_n_groups * c.mamba_d_state
    ks = jax.random.split(key, 16)

    def nrm(k, shape, mult=1.0, std=0.02):
        return (jax.random.normal(k, shape, jnp.float32)
                * (std / mult)).astype(c.dtype)

    # one deviation a segment of the mixer's projection: [z | x B C | dt]
    seg = 1.0 / (c.ssm_in_multiplier * _mup_vector(c, jnp.float32))
    dt = jnp.exp(jax.random.uniform(ks[12], (L, H), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    params = {
        "embed": nrm(ks[0], (V, D), c.embedding_multiplier),
        "layers": {
            "ln1": jnp.ones((L, D), c.dtype),
            "wq": nrm(ks[1], (L, D, nh * hd), c.attention_in_multiplier,
                      std=0.04),
            "wk": nrm(ks[2], (L, D, nkv * hd),
                      c.attention_in_multiplier * c.key_multiplier),
            "wv": nrm(ks[3], (L, D, nkv * hd), c.attention_in_multiplier),
            "wo": nrm(ks[4], (L, nh * hd, D), c.attention_out_multiplier),
            "in_proj": (jax.random.normal(ks[5], (L, 2 * ds + 2 * gn + H, D),
                                          jnp.float32)
                        * 0.02 * seg[:, None]).astype(c.dtype),
            "conv_w": nrm(ks[6], (L, K, cd), std=K ** -0.5),
            "conv_b": jnp.zeros((L, cd), c.dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.log(jax.random.uniform(ks[13], (L, H), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((L, H), jnp.float32),
            "norm": jnp.ones((L, ds), c.dtype),
            "out_proj": nrm(ks[7], (L, ds, D), c.ssm_out_multiplier),
            "ln2": jnp.ones((L, D), c.dtype),
            "gate": nrm(ks[8], (L, D, Ff), c.mlp_multipliers[0]),
            "up": nrm(ks[9], (L, D, Ff)),
            "down": nrm(ks[10], (L, Ff, D), c.mlp_multipliers[1]),
        },
        "ln_f": jnp.ones((D,), c.dtype),
    }
    if not c.tie_word_embeddings:
        params["lm_head"] = nrm(ks[11], (V, D), c.lm_head_multiplier)
    return params


# ---------------------------------------------------------------------------
# the pieces the paged programs reach by name
# ---------------------------------------------------------------------------

def _head(params, config: FalconH1Config):
    return params["embed"] if config.tie_word_embeddings \
        else params["lm_head"]


def embed_tokens(params, ids, config: FalconH1Config):
    x = jnp.take(params["embed"], ids, axis=0)
    return x * jnp.asarray(config.embedding_multiplier, x.dtype)


def head_logits(params, x, config: FalconH1Config):
    """Float32 logits of hidden states that passed the last norm."""
    return _head_logits(x, _head(params, config)) * config.lm_head_multiplier


def state_shapes(config: FalconH1Config) -> Dict[str, tuple]:
    """What a sequence keeps beside its keys and values, a layer: leaf
    name -> (shape, type). The recurrent state is float32 whatever the
    model's type: it is an accumulator rounded once a token for thousands
    of tokens. A head's state lies ``[d_state, head_dim]`` and the
    convolution's tail ``[d_conv - 1, channels]``, the long axis on the
    lanes (``kernels/ssm.py``)."""
    c = config
    return {"ssm": ((c.mamba_n_heads, c.mamba_d_state, c.mamba_d_head),
                    jnp.float32),
            "conv": ((c.mamba_d_conv - 1, c.conv_dim), c.dtype)}


@jax.named_scope("mlp")
def decode_mlp(x, lp, config: FalconH1Config):
    """ln2 + SwiGLU + residual, the gate's input and the output scaled."""
    c = config
    g = _rms(x, lp["ln2"], c.rms_norm_eps)
    gate = _mm(g, lp["gate"]) * jnp.asarray(c.mlp_multipliers[0], x.dtype)
    y = _mm(jax.nn.silu(gate) * _mm(g, lp["up"]), lp["down"])
    return x + y * jnp.asarray(c.mlp_multipliers[1], x.dtype)


# ---------------------------------------------------------------------------
# the mixer
#
# Every op of it is traced under the scope ``ssm`` and, inside that, one of
# ``ssm.proj`` (the two projections, the muP vector, the gate and the
# grouped norm), ``ssm.conv``, ``ssm.scan`` (prefill) and ``ssm.update``
# (decode): a metric reads the whole mixer through the outer name, a
# person the split by the inner ones (docs/observability.md).
# ---------------------------------------------------------------------------

def _mup_vector(c: FalconH1Config, dtype):
    gn = c.mamba_n_groups * c.mamba_d_state
    return jnp.concatenate([
        jnp.full((n,), m, dtype) for n, m in zip(
            (c.mamba_d_ssm, c.mamba_d_ssm, gn, gn, c.mamba_n_heads),
            c.ssm_multipliers)])


@jax.named_scope("ssm.proj")
def _in_proj(h, lp, c: FalconH1Config):
    """[.., D] -> the gate z, the convolution's input [x B C], dt."""
    u = jnp.einsum("...d,nd->...n",
                   h * jnp.asarray(c.ssm_in_multiplier, h.dtype),
                   lp["in_proj"]) * _mup_vector(c, h.dtype)
    ds = c.mamba_d_ssm
    return u[..., :ds], u[..., ds:ds + c.conv_dim], u[..., ds + c.conv_dim:]


def _split_xbc(xbc, c: FalconH1Config):
    """The convolution's output as x [.., H, P], B and C [.., G, N]."""
    ds, gn = c.mamba_d_ssm, c.mamba_n_groups * c.mamba_d_state
    lead = xbc.shape[:-1]
    x = xbc[..., :ds].reshape(*lead, c.mamba_n_heads, c.mamba_d_head)
    b, cc = (t.reshape(*lead, c.mamba_n_groups, c.mamba_d_state)
             for t in (xbc[..., ds:ds + gn], xbc[..., ds + gn:]))
    return x, b, cc


def _step_sizes(dt, lp):
    """dt = softplus(dt + dt_bias) and A = -exp(A_log), float32."""
    return (jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"]),
            -jnp.exp(lp["A_log"].astype(jnp.float32)))


@jax.named_scope("ssm.proj")
def _gate_out(y, x, z, lp, c: FalconH1Config):
    """The skip ``D x``, the gate, the grouped RMSNorm (a mean square a
    group of ``d_ssm / n_groups`` channels), the projection out."""
    f32 = jnp.float32
    y = y + lp["D"].astype(f32)[:, None] * x.astype(f32)
    lead = z.shape[:-1]
    y = y.reshape(*lead, c.mamba_d_ssm) * jax.nn.silu(z.astype(f32))
    yg = y.reshape(*lead, c.mamba_n_groups, -1)
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                        + c.rms_norm_eps)
    y = (yg.reshape(*lead, c.mamba_d_ssm) * lp["norm"].astype(f32)
         ).astype(z.dtype)
    return _mm(y, lp["out_proj"]) * jnp.asarray(c.ssm_out_multiplier,
                                                z.dtype)


@jax.named_scope("ssm")
def mixer_prefill(h, lp, config: FalconH1Config, slen):
    """Whole sequences ``h`` [G, S, D], row g valid up to ``slen[g]``.
    Returns (m [G, S, D], the state each row is in after ``slen[g]``
    tokens: ``{"ssm": [G, H, N, P], "conv": [G, d_conv-1, channels]}``).
    A padded token takes a zero step, so it neither decays nor adds."""
    from ..kernels.ssm import ssd_chunked_scan

    c = config
    S, K = h.shape[1], c.mamba_d_conv
    z, xbc, dt = _in_proj(h, lp, c)
    with jax.named_scope("ssm.conv"):
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        conv = sum(xp[:, j:j + S] * lp["conv_w"][j] for j in range(K))
        x, b, cc = _split_xbc(jax.nn.silu(conv + lp["conv_b"]), c)
        # the K-1 inputs before position slen (zeros before the start)
        tail = jnp.take_along_axis(
            xp, (slen[:, None] + jnp.arange(K - 1))[:, :, None], axis=1)
    step, a = _step_sizes(dt, lp)
    step = jnp.where((jnp.arange(S) < slen[:, None])[..., None], step, 0.0)
    y, last = ssd_chunked_scan(x, step, a, b, cc, c.mamba_chunk_size)
    return _gate_out(y, x, z, lp, c), {"ssm": last, "conv": tail}


@jax.named_scope("ssm")
def mixer_decode(h, lp, config: FalconH1Config, state, layer, rows):
    """One token a slot: ``h`` [B, 1, D] against ``state`` (the leaves of
    ``state_shapes`` with leading axes [layers, rows]), slot i's row
    ``rows[i]``; a slot with nothing to keep names the last row, which no
    sequence owns. Returns (m [B, 1, D], the state with layer ``layer``'s
    rows updated in place)."""
    from ..kernels import dispatched_ssm_update

    c = config
    z, xbc, dt = _in_proj(h, lp, c)
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate(
            [state["conv"][layer, rows].astype(xbc.dtype), xbc], axis=1)
        conv = jnp.einsum("bkc,kc->bc", window, lp["conv_w"])
        x, b, cc = _split_xbc(jax.nn.silu(conv + lp["conv_b"]), c)
        tails = state["conv"].at[layer, rows].set(
            window[:, 1:].astype(state["conv"].dtype))
    with jax.named_scope("ssm.update"):
        step, a = _step_sizes(dt[:, 0], lp)                      # [B, H]
        ssm, y = dispatched_ssm_update(
            state["ssm"], layer, rows, jnp.exp(step * a),
            step[..., None] * x.astype(jnp.float32), b, cc)
    m = _gate_out(y[:, None], x[:, None], z, lp, c)
    return m, {"ssm": ssm, "conv": tails}


# ---------------------------------------------------------------------------
# the block, and whole sequences
# ---------------------------------------------------------------------------

def paged_block(x, lp, config: FalconH1Config, cos, sin, attend, mix):
    """One block round the caller's attention core and mixer (the seam of
    ``inference/paged.py``): ``attend(q, k, v) -> (a [B, S, heads x
    head_dim], its own extra)``, ``mix(h, lp) -> (m [B, S, D], its own
    extra)``. Both read the same normed input and land in one add."""
    c = config
    with jax.named_scope("attn.proj"):
        h = _rms(x, lp["ln1"], c.rms_norm_eps)
        q, k, v = _qkv_proj(
            h * jnp.asarray(c.attention_in_multiplier, h.dtype), lp, c)
        q = rope_raw(q, cos, sin)
        k = rope_raw(k * jnp.asarray(c.key_multiplier, k.dtype), cos, sin)
    a, kv = attend(q, k, v)
    m, st = mix(h, lp)
    with jax.named_scope("attn.proj"):
        a = _mm(a.astype(x.dtype), lp["wo"]) \
            * jnp.asarray(c.attention_out_multiplier, x.dtype)
        x = x + a + m
    return decode_mlp(x, lp, c), kv, st


def forward(params, ids, config: FalconH1Config):
    """Logits [B, S, V] of whole sequences [B, S]: the paged prefill's
    block with plain causal attention and no cache."""
    c = config
    B, S = ids.shape
    x = embed_tokens(params, ids, c)
    cos, sin = rope_tables(S, c.head_dim, theta=c.rope_theta)
    slen = jnp.full((B,), S, jnp.int32)

    def attend(q, k, v):
        with jax.named_scope("attn.kernel"):
            return sdpa_raw(q, k, v, is_causal=True).reshape(B, S, -1), None

    def step(x, lp):
        x, _, _ = paged_block(x, lp, c, cos, sin, attend,
                              lambda h, lp: mixer_prefill(h, lp, c, slen))
        return x, None

    x, _ = lax.scan(step, x, params["layers"])
    return head_logits(params, _rms(x, params["ln_f"], c.rms_norm_eps), c)
