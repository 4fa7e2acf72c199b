"""The yardstick's arithmetic: peaks, parameter counts, model FLOPs a
token, cache bytes a token, and what each kernel call has to compute or
read. Everything here is a function of shapes, so that a program PR
cannot move it. Peaks are the published figures of the part, keyed by
the ``device_kind`` JAX reports; a device that is not in the table is an
error, never a default.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, a chip. ``"TPU v5 lite"`` is what a v5e reports (PR 21).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to work.PEAKS with its "
                       f"source")
    return PEAKS[device_kind]


# -- parameters --------------------------------------------------------

def _attn_params(c: dict) -> int:
    d, nh, nkv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = d // nh
    return d * hd * (nh + 2 * nkv) + nh * hd * d


def dense_layer_params(c: dict) -> int:
    """One decoder layer of a dense SwiGLU model, norms included."""
    return (_attn_params(c) + 3 * c["hidden_size"] * c["intermediate_size"]
            + 2 * c["hidden_size"])


def moe_layer_params(c: dict, active: bool = False) -> int:
    """One DeepSeekMoE expert layer: attention, router, routed experts
    (all of them, or the ``num_experts_per_tok`` a token uses), shared
    experts, norms."""
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    routed = c["num_experts_per_tok"] if active else c["n_routed_experts"]
    return (_attn_params(c) + d * c["n_routed_experts"]
            + routed * 3 * d * fe
            + 3 * d * c["n_shared_experts"] * fe + 2 * d)


def param_count(c: dict, active: bool = False) -> int:
    """Parameters of the configuration as run (its file's own keys). With
    ``active``: those a token's forward pass multiplies by, the embedding
    lookup left out."""
    layer = moe_layer_params(c, active) if "n_routed_experts" in c \
        else dense_layer_params(c)
    table = c["vocab_size"] * c["hidden_size"]
    tables = table if active or c.get("tie_word_embeddings") else 2 * table
    return c["num_hidden_layers"] * layer + tables + c["hidden_size"]


def model_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token requires: 6 a
    multiplied parameter, plus causal attention's two products at
    ``seq_len`` (forward 4*S/2*heads*head_dim a layer, backward twice
    that). Recompute is not counted."""
    d = c["hidden_size"]
    attn = 6.0 * c["num_hidden_layers"] * seq_len * d
    return 6.0 * param_count(c, active=True) + attn


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    hd = c["hidden_size"] // c["num_attention_heads"]
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"] * hd
            * bytes_per_value)


# -- kernels: what one call has to do -----------------------------------

def flash_unit_flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    """One causal [S, S] product over all heads: 2*B*H*S*S*d / 2."""
    return float(batch) * heads * seq * seq * head_dim


def flash_shape(c: dict, mix: dict) -> dict:
    """B, H, S, d of a training step's flash calls: the mix's batch and
    sequence length, the configuration's heads."""
    return {"batch": mix["batch"], "seq": mix["seq_len"],
            "heads": c["num_attention_heads"],
            "head_dim": c["hidden_size"] // c["num_attention_heads"]}


def flash_flops(calls: dict, *, batch, heads, seq, head_dim) -> float:
    """FLOPs the flash calls in a trace had to do. A forward call is two
    products (QK^T, PV); a backward pass is five (QK^T again, dP, dV, dK,
    dQ), whatever number of kernels the program splits it into: the
    backward's share is counted once per ``bwd`` call."""
    u = flash_unit_flops(batch, heads, seq, head_dim)
    return (2 * calls.get("fwd", 0) + 5 * calls.get("bwd", 0)) * u


def paged_attn_bytes(kv_token_steps: float, c: dict) -> float:
    """Bytes the paged decode kernel had to read: every live cached token
    of every slot, K and V, once a layer a decode step.
    ``kv_token_steps`` is the sum over decode steps of live tokens."""
    return kv_token_steps * kv_bytes_per_token(c)
