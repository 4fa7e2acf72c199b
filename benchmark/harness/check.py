"""The comparison that decides ``correct``: the program against the plain
reference, during set-up, at the widths the cell runs. Logits and losses
are compared, never sampled tokens: with random weights the largest
logit changes on rounding.

The reference is the configuration's own: ``arch`` is the module its
file names (``benchmark/architectures/<name>.py``), which gives the
reference's ``layer``, ``logits_at`` and ``loss`` and, for serving, the
three calls into the program over a cache this file never opens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .traffic import seed_of


def _leaf_norms(tree) -> dict:
    """The norm of each leaf of a gradient, by the leaf's path."""
    return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
        g.astype(jnp.float32))))
        for k, g in jax.tree_util.tree_leaves_with_path(tree)}


def serve_check(arch, family, cfg, conf: dict, params, page_size: int,
                seed: int, reference_params=None) -> dict:
    """The engine's own programs (``arch.prefill``, then greedy
    ``arch.decode_step``s, through the cache ``arch.make_cache`` makes:
    one pytree, handed back in and donated, never opened) against the
    reference's full forward pass over prompt plus the tokens decoded.
    Two errors, each with its band: the largest logit difference over the
    largest reference logit (one logit far off), and the difference's rms
    over the logits' rms (the whole pass a little off; the steadier of
    the two). ``reference_params`` (a test's) gives the reference other
    weights than the program."""
    chk = conf["serve"]["check"]
    n, plen, steps = chk["prompts"], chk["prompt_len"], chk["decode_steps"]
    ps = page_size
    s_pad = -(-plen // ps) * ps
    per_seq = -(-(plen + steps) // ps)
    rows = np.arange(n * per_seq, dtype=np.int32).reshape(n, per_seq)
    ids = seed_of(seed, 4).integers(0, cfg.vocab_size, (n, plen),
                                    dtype=np.int32)
    padded = np.zeros((n, s_pad), np.int32)
    padded[:, :plen] = ids
    cache = arch.make_cache(cfg, n * per_seq, ps, n)

    prefill = jax.jit(
        lambda p, i, c, r, sl: arch.prefill(family, p, i, cfg, c, r, sl),
        donate_argnums=(2,))
    decode = jax.jit(
        lambda p, c, bt, ln, tok: arch.decode_step(family, p, c, bt, ln,
                                                   tok, cfg),
        donate_argnums=(1,))
    cache, logits = prefill(params, jnp.asarray(padded), cache,
                            jnp.asarray(rows[:, :s_pad // ps]),
                            jnp.full((n,), plen, jnp.int32))
    got, toks = [np.asarray(logits, np.float32)], []
    for t in range(steps):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        cache, logits = decode(params, cache, jnp.asarray(rows),
                               jnp.full((n,), plen + t + 1, jnp.int32), tok)
        got.append(np.asarray(logits, np.float32))
    got = np.stack(got, 1)                                # [n, steps+1, V]
    del cache

    layer_fn = jax.jit(lambda x, w: arch.layer(x, w, conf))
    want = []
    with jax.default_matmul_precision("highest"):
        for j in range(n):
            full = np.concatenate([ids[j], [tk[j] for tk in toks]])
            want.append(np.asarray(arch.logits_at(
                reference_params or params, jnp.asarray(full), conf,
                np.arange(plen - 1, plen + steps), layer_fn)))
    want = np.stack(want)
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want))) / scale
    rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    ok = bool(np.isfinite(got).all() and err <= chk["tolerance"]
              and rms <= chk["rms_tolerance"])
    return {"ok": ok, "logit_err_over_max": err, "max_ref_logit": scale,
            "rms_err_over_rms": rms, "tolerance": chk["tolerance"],
            "rms_tolerance": chk["rms_tolerance"],
            "numbers": {"logit_err_over_max": [err, chk["tolerance"]],
                        "rms_err_over_rms": [rms, chk["rms_tolerance"]]},
            "compared": f"{n} prompts x {plen} tokens, prefill + {steps} "
                        f"decode steps"}


def train_check(arch, family, cfg, conf: dict, params, seed: int) -> dict:
    """First-step loss and the gradient on one sequence: the program's
    ``loss_fn`` (kernels, fused CE, capacity dispatch) against the
    reference's loss under the same capacity rule. The gradient is
    compared leaf by leaf, by norm: a fault in a small leaf (a router, a
    norm's gain) vanishes in the global norm beside the tables."""
    chk = conf["train"]["check"]
    ids = seed_of(seed, 4).integers(0, cfg.vocab_size,
                                    (1, chk["seq_len"] + 1), dtype=np.int32)

    def measured(loss_of):
        def f(p, b):
            loss, grads = jax.value_and_grad(lambda q: loss_of(q, b))(p)
            return loss.astype(jnp.float32), _leaf_norms(grads)
        return jax.jit(f)

    loss, norms = measured(lambda q, b: family.loss_fn(q, b, cfg))(
        params, jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        rloss, rnorms = measured(lambda q, b: arch.loss(q, b, conf))(
            params, jnp.asarray(ids[0]))
    loss, rloss = float(loss), float(rloss)
    norms, rnorms = ({k: float(v) for k, v in t.items()}
                     for t in (norms, rnorms))
    gnorm, rgnorm = (float(np.sqrt(sum(v * v for v in t.values())))
                     for t in (norms, rnorms))
    dl, dg = abs(loss - rloss), abs(gnorm - rgnorm) / rgnorm
    leaf = {k: abs(norms[k] - rnorms[k]) / rnorms[k] for k in rnorms}
    # every leaf has the band ``leaf_norm_tolerance`` but those given one
    # of their own; the worst leaf is the one that fills most of its band
    band = {k: chk.get("leaf_norm_tolerance_of", {}).get(
        k, chk["leaf_norm_tolerance"]) for k in leaf}
    worst = max(leaf, key=lambda k: leaf[k] / band[k])
    ok = bool(np.isfinite([loss, gnorm]).all()
              and dl <= chk["loss_tolerance"]
              and dg <= chk["grad_norm_tolerance"]
              and leaf[worst] <= band[worst])
    return {"ok": ok, "loss": loss, "ref_loss": rloss, "grad_norm": gnorm,
            "ref_grad_norm": rgnorm, "loss_abs_diff": dl,
            "grad_norm_rel_diff": dg, "worst_leaf": worst,
            "worst_leaf_norm_rel_diff": leaf[worst],
            "worst_leaf_band": band[worst],
            "numbers": {"loss_abs_diff": [dl, chk["loss_tolerance"]],
                        "grad_norm_rel_diff": [dg,
                                               chk["grad_norm_tolerance"]],
                        "worst_leaf_norm_rel_diff": [leaf[worst],
                                                     band[worst]]},
            "leaf_norm_rel_diff": {k: round(v, 6) for k, v in leaf.items()},
            "compared": f"one sequence of {chk['seq_len']} tokens"}
