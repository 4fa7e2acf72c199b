"""The declaration of a stack that is not one run of like layers.

A family whose layers are all alike stacks them under ``params["layers"]``
and the paged programs (``inference/paged.py``) run one scan over them. A
family of several kinds of layer gives ``segments(config)``: a tuple of
``Segment`` in the stack's order. The programs run a scan a segment over
``params[kind]`` (that kind's layers stacked on axis 0) with the whole
cache as the carry, and the cache reads the same tuple for how many
layers keep what: ``"pages"`` (a layer of the page pool), ``"ring"`` (a
row of a window's ring a sequence), ``"state"`` (a row of recurrent state
a sequence), or nothing (a layer that reads what another layer keeps).
``last_only`` marks a segment that a prefill runs on each prompt's last
position alone: nothing a later token needs is made there.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple


class Segment(NamedTuple):
    kind: str
    count: int
    keeps: Tuple[str, ...] = ()
    last_only: bool = False
