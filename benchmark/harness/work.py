"""The yardstick's arithmetic: peaks, what the architectures' counts
share (attention's parameters, a decoder's tables, FLOPs a trained token,
cache bytes a token), and what each kernel call has to compute or read.
Everything here is a function of shapes, so that a program PR cannot
move it. Peaks are the published figures of the part, keyed by
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


# -- what architectures count with ----------------------------------------
# (an architecture's own counts are in ``benchmark/architectures/<name>.py``:
# ``param_count``, ``kv_bytes_per_token``, ``model_flops_per_token``,
# ``state_bytes_per_slot``; these are the parts several of them share)

def head_dim(c: dict) -> int:
    """A head's size: the file's ``head_dim`` where it states one, else
    ``hidden_size // num_attention_heads``."""
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def attn_params(c: dict) -> int:
    """The four projections of grouped-query attention, no bias."""
    d, nh, nkv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    hd = head_dim(c)
    return d * hd * (nh + 2 * nkv) + nh * hd * d


def decoder_params(c: dict, layer: int, active: bool = False) -> int:
    """``num_hidden_layers`` layers of ``layer`` parameters each, the
    embedding and the head (one table where they are tied), the last
    norm. With ``active``: those a token's forward pass multiplies by,
    the embedding lookup left out."""
    table = c["vocab_size"] * c["hidden_size"]
    tables = table if active or c.get("tie_word_embeddings") else 2 * table
    return c["num_hidden_layers"] * layer + tables + c["hidden_size"]


def train_flops_per_token(c: dict, active_params: int, seq_len: int) -> float:
    """Forward and backward FLOPs a trained token requires: 6 a
    multiplied parameter, plus causal attention's two products at
    ``seq_len`` (forward 4*S/2*heads*head_dim a layer, backward twice
    that). Recompute is not counted."""
    attn = 6.0 * c["num_hidden_layers"] * seq_len \
        * c["num_attention_heads"] * head_dim(c)
    return 6.0 * active_params + attn


def kv_bytes_per_token(c: dict, bytes_per_value: int = 2) -> int:
    """Keys and values of one cached token where every layer attends."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * head_dim(c) * bytes_per_value)


# -- kernels: what one call has to do -----------------------------------

def flash_unit_flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    """One causal [S, S] product over all heads: 2*B*H*S*S*d / 2."""
    return float(batch) * heads * seq * seq * head_dim


def flash_shape(c: dict, mix: dict) -> dict:
    """B, H, S, d of a training step's flash calls: the mix's batch and
    sequence length, the configuration's heads."""
    return {"batch": mix["batch"], "seq": mix["seq_len"],
            "heads": c["num_attention_heads"], "head_dim": head_dim(c)}


def flash_flops(calls: dict, *, batch, heads, seq, head_dim) -> float:
    """FLOPs the flash calls in a trace had to do. A forward call is two
    products (QK^T, PV); a backward pass is five (QK^T again, dP, dV, dK,
    dQ), whatever number of kernels the program splits it into: the
    backward's share is counted once per ``bwd`` call."""
    u = flash_unit_flops(batch, heads, seq, head_dim)
    return (2 * calls.get("fwd", 0) + 5 * calls.get("bwd", 0)) * u


def decode_attn_flops(kv_token_steps: float, c: dict) -> float:
    """FLOPs of decode attention's two products (q K^T and p V, two a
    multiply-add) over ``kv_token_steps`` live cached tokens read, every
    query head a layer."""
    return (4.0 * kv_token_steps * c["num_hidden_layers"]
            * c["num_attention_heads"] * head_dim(c))
