"""The three calls the serve check makes into the program, for an
architecture whose cache is keys and values in a paged pool:
``inference/paged.py``'s ``init_pool``, ``paged_prefill`` and
``paged_decode_step``, with the two pool halves as one pytree. The check
hands that pytree back in, never opens it, and donates it; an
architecture that caches more (a recurrent state a slot, say) gives the
same three calls over a pytree of its own.
"""
from __future__ import annotations


def make_cache(cfg, num_pages: int, page_size: int, sequences: int):
    """The cache of ``sequences`` sequences over ``num_pages`` pages."""
    from paddle_tpu.inference.paged import init_pool

    return init_pool(cfg, num_pages, page_size)


def prefill(family, params, ids, cfg, cache, page_rows, slen):
    """Padded prompts [G, S_pad] into ``page_rows``: (cache, logits [G, V]
    at each row's position ``slen`` - 1)."""
    from paddle_tpu.inference.paged import paged_prefill

    pk, pv, logits = paged_prefill(family, params, ids, cfg, cache["k"],
                                   cache["v"], page_rows, slen)
    return {"k": pk, "v": pv}, logits


def decode_step(family, params, cache, block_tables, lengths, tokens, cfg):
    """One token a sequence at position ``lengths`` - 1: (cache, logits)."""
    from paddle_tpu.inference.paged import paged_decode_step

    pk, pv, logits = paged_decode_step(family, params, cache["k"],
                                       cache["v"], block_tables, lengths,
                                       tokens, cfg)
    return {"k": pk, "v": pv}, logits
