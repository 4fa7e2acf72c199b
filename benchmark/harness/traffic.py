"""One general traffic generator: a mix's data file in, a schedule out.

Every seed gets the same work. Lengths are not drawn: they are the
quantiles of the mix's distributions, one for each request, in an order
the mix's file fixes; ``--seed`` makes the token ids (and the weights).
So two seeds differ in what is said, never in how much there is to do or
in what meets what, and a run's spread is the system's, not the sample's.

Copied in idea from ``paddle_tpu/loadgen/traces.py::generate_trace``
(lengths as a pure function of a seed); that one draws, this one takes
quantiles, and it is kept here so that a program PR cannot move the
yardstick.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_N = NormalDist()


def seed_of(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one run. ``--seed`` may be wider
    than 32 bits; SeedSequence takes any non-negative whole number."""
    return np.random.default_rng([abs(int(seed))] + [int(s) for s in stream])


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: the (i + 1/2)/n quantiles of a lognormal with the
    given median and sigma, clipped to [min, max], as whole numbers."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    q = np.array([_N.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(mu + sigma * q)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def closed_schedule(mix: dict, clients: int) -> dict:
    """Closed loop: ``requests_per_client`` x ``clients`` requests handed
    out in order as clients come free. The first ``clients`` of them are
    met part-way through: a request at phase u has the first u of its
    output already in its prompt, so that the window opens on slots of
    every age and not on a wave that started together.

    The order is the mix's own (``order_seed`` in its file), the same for
    every ``--seed``: a window meets only the head of the list (the first
    round and the few requests that follow its completions), so an order
    drawn from the seed gave each seed other completions and other
    prefills in its window, and tokens a second that differed by 1.6%
    between seeds against 0.05% between two runs of one seed (PR 23)."""
    later = clients * (mix["requests_per_client"] - 1)
    rng = seed_of(mix["order_seed"], 1)
    # the first round and the later ones are each a fixed set of lengths
    prompt, out = (np.concatenate([
        rng.permutation(lognormal_quantiles(mix[k], clients)),
        rng.permutation(lognormal_quantiles(mix[k], later))])
        for k in ("prompt", "output"))
    phase = rng.permutation((np.arange(clients) + 0.5) / clients)
    done = np.minimum(np.floor(phase * out[:clients]).astype(np.int64),
                      out[:clients] - 2)
    prompt[:clients] += done
    out[:clients] -= done
    return {"prompt_len": prompt, "out_len": out}


def prompt_ids(lengths: np.ndarray, vocab: int, seed: int) -> list:
    """All prompts' token ids in one draw, split by request."""
    flat = seed_of(seed, 2).integers(0, vocab, int(lengths.sum()),
                                     dtype=np.int32)
    return np.split(flat, np.cumsum(lengths)[:-1])


def train_batches(mix: dict, vocab: int, seed: int):
    """An endless stream of [batch, seq_len + 1] id arrays, uniform over
    the vocabulary, a new one every step."""
    rng = seed_of(seed, 3)
    shape = (mix["batch"], mix["seq_len"] + 1)
    while True:
        yield rng.integers(0, vocab, shape, dtype=np.int32)
