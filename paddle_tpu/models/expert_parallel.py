"""One chip's share of an expert-parallel layer, as the families that hold
part of a layer's experts compute it (``models/axk1.py``,
``models/command_a.py``).

The router runs over all ``n_router_outputs`` experts with the published
rule (``models/moe.py::_route``: the config's ``scoring_func``,
``n_group`` / ``topk_group``, ``norm_topk_prob``,
``routed_scaling_factor``); the chip computes the picks among the experts
it holds, ``[first_held_expert, first_held_expert + n_routed_experts)``,
through the expert kernel (``kernels/moe_experts.py``), and what the
experts held elsewhere would add is another chip's term of the same sum.
Nothing here stands in for the absent chips or for the all-to-all.
"""
from __future__ import annotations

import jax

from .moe import _route

__all__ = ["EXPERT_TOKENS", "pieces", "routed"]

# Tokens a call of the expert kernel takes: its row layout is sized for
# every pick held (a 16,384-token prompt's 131,072 picks would be 1.9 GB
# of rows at A.X K1's width), so a long prefill goes through in pieces.
EXPERT_TOKENS = 2048


def pieces(f, n: int, *rows):
    """``f`` over rows [T, ..] in pieces of ``n`` (one call where T is at
    most ``n`` or not a multiple of it): [T, ..]."""
    T = rows[0].shape[0]
    if T <= n or T % n:
        return f(*rows)
    y = jax.lax.map(lambda r: f(*r), tuple(
        a.reshape((T // n, n) + a.shape[1:]) for a in rows))
    return y.reshape((T,) + y.shape[2:])


def routed(h, lp, c, name, layer):
    """The held picks' weighted sum [T, D] of rows ``h`` [T, D], and the
    picks [T, k] (the router's outputs, held or not). ``lp`` holds the
    layer's ``router`` and every layer's held experts (``experts``:
    ``gate``, ``up``, ``down`` [L, E, F, D]); ``layer`` names this one;
    ``name`` is the kernel's name in a trace."""
    from ..kernels import dispatched_expert_mlp

    with jax.named_scope("moe.route"):
        w, e, _ = _route(h, lp, c)
    ex = lp["experts"]

    def call(hc, wc, ec):
        return dispatched_expert_mlp(
            hc, ec, ex["gate"], ex["up"], ex["down"], layer, weight=wc,
            first=c.first_held_expert, routed=c.n_router_outputs, name=name)

    return pieces(call, EXPERT_TOKENS, h, w, e), e
