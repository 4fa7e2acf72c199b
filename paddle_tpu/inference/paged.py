"""Paged KV cache: page-pool tensors + block-table allocator + the
paged prefill/decode data plane.

Reference capability: vLLM's PagedAttention block manager (the
dominant serving-stack design: KV lives in fixed-size pages named by
per-sequence block tables, so HBM is allocated at page granularity
instead of max-length ring buffers) realised TPU-native per Ragged
Paged Attention (arxiv 2604.15464, PAPERS.md).

Three layers:

- ``PageAllocator`` — the host-side control plane: a free list plus
  ref-counted pages per sequence (alloc / ensure(+copy-on-write) /
  advance / fork / free). Pure Python+numpy; never touches the device.
- ``PagedKVCache`` — the pool tensors (one page grid per layer) married
  to an allocator; owns layout and the block-table/length device views.
- ``cache_prefill`` / ``cache_decode_step`` / ``cache_prefill_shared`` /
  ``cache_verify_window`` — pure-jax data plane over the WHOLE cache (one
  pytree, taken and returned), generic over the model family: any module
  exposing the decoder seam (``_qkv_proj``-compatible layers,
  ``decode_mlp``, ``_head``) plugs in — llama and the MoE families both
  do. A family whose block is not "attention, then MLP" composes it
  itself round the program's attention core (``paged_block``, see
  ``_block``); one that keeps a recurrent state a sequence declares its
  leaves (``state_shapes``) and gives the mixer in two forms
  (``mixer_prefill``, ``mixer_decode``): models/falcon_h1.py does both.
  A family whose stack is NOT one run of like layers declares it
  (``segments(config)``, ``models/stack.py``): the two programs then run
  a scan a segment with the whole cache as the carry, and the cache reads
  the same declaration for how many layers keep pages, a ring row or a
  state row (``_StackOps``; models/phi4flash.py: one pool layer that eight
  layers read, a ring a window layer, a state a Mamba-1 layer).
  A family whose layers hand a second vector a token from one to the
  next gives ``stream(x, config)`` (the layer scan's carry in place of
  the residual stream alone), may keep weights out of the scan
  (``params["experts"]``, read where they lie, by layer) and may return
  a fourth item from ``paged_block``, which the programs hand back where
  asked (``routes=True``): models/zaya.py, whose router carries its input
  through the layers and whose blocks say which expert each token took.
  A family whose cache is ONE leaf (``pool_layout``'s fourth item names
  the leaves: models/axk1.py's ``latent``, a row ``[c ; k_pe]`` a token
  that multi-head latent attention reads for keys and values both)
  writes and reads it through ``_StackOps.attend_latent``.
  ``paged_prefill`` / ``paged_decode_step`` are the same two programs for
  a caller that holds the two pool halves and nothing else.

Two kinds of state under one manager: K/V pages a token, and (for a
family that declares one) a state a SEQUENCE, recurrent or a window's
ring of keys and values: leaves ``cache["state"][name]`` of shape ``[L,
rows + 1, ...]`` (``L`` the layers that keep that leaf). A sequence owns
one row from ``alloc`` to ``free``; slots reach their rows through a row
table (a block table of width 1), so moving a sequence to another slot
copies nothing. The last row belongs to nobody: a prefill group's dummy
rows and a decode grid's idle slots name it, and what lands there is
never read.

Pool layout: ``[L, num_pages, kv_heads, page_size, head_dim]``. The
ISSUE/vLLM order puts page_size before kv_heads; the kv-head axis is
hoisted OUTSIDE the page axis here so the decode kernel's per-page
block ``(1, 1, page_size, head_dim)`` satisfies Mosaic's last-two-dims
tiling rule for every page size (see kernels/paged_attention.py).

Writes into pages use scatter-with-drop: block-table entries equal to
``num_pages`` are an explicit "no page" sentinel, so a padded prompt
page or an inactive decode slot drops its write instead of corrupting
page 0 — the allocator owns the sentinel discipline.
"""
from __future__ import annotations

import functools
import itertools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import enforce as E
from ..models.llama import _head_logits, _mm, _qkv_proj, _rms
from ..nn.functional.attention import rope_raw, rope_tables

__all__ = ["PageAllocator", "PagedKVCache", "PrefixCache", "init_pool",
           "cache_prefill", "cache_decode_step", "cache_prefill_shared",
           "cache_verify_window", "paged_prefill", "paged_decode_step"]


# ---------------------------------------------------------------------------
# host-side control plane
# ---------------------------------------------------------------------------

class PageAllocator:
    """Free-list page allocator with per-sequence block tables and
    ref-counted pages (copy-on-fork for beam/top-k style sequence
    sharing). All methods are host-side and O(pages touched); OOM is a
    ``None`` return with state unchanged — admission control, not an
    exception."""

    def __init__(self, num_pages: int, page_size: int,
                 max_pages_per_seq: int, state_rows: int = 0):
        E.enforce(num_pages >= 1, f"num_pages must be >= 1, got {num_pages}")
        E.enforce(page_size >= 1, f"page_size must be >= 1, got {page_size}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_seq = int(max_pages_per_seq)
        # rows of recurrent state (0: the family keeps none). A sequence
        # takes one with its first pages and gives it back with them, so
        # every path that frees a sequence frees its row.
        self.state_rows = int(state_rows)
        self._free_rows: List[int] = list(range(self.state_rows - 1, -1, -1))
        self.rows_assigned = 0
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._ref = np.zeros(num_pages, np.int32)
        # prefix-cache pins: each held page carries exactly one extra
        # ref owned by the radix cache (0/1 per page), so
        # seq-held-counts + cache-holds == _ref stays auditable
        self._cache_hold = np.zeros(num_pages, np.int32)
        # seq_id -> {"pages": [page ids], "len": tokens written}
        self._seqs: Dict[int, dict] = {}

    # -- introspection ------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def used_rows(self) -> int:
        return self.state_rows - len(self._free_rows)

    def state_row(self, seq_id: int) -> int:
        """The row of recurrent state this sequence owns."""
        return self._seqs[seq_id]["row"]

    def pages_for(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    def seq_len(self, seq_id: int) -> int:
        return self._seqs[seq_id]["len"]

    def seq_pages(self, seq_id: int) -> List[int]:
        return list(self._seqs[seq_id]["pages"])

    def page_count(self, seq_id: int) -> int:
        """Pages currently held by this sequence (no list copy — the
        engine's per-chunk cost attribution reads it per live slot)."""
        return len(self._seqs[seq_id]["pages"])

    def block_row(self, seq_id: int, width: Optional[int] = None
                  ) -> np.ndarray:
        """This sequence's block-table row, padded with the ``num_pages``
        sentinel (the no-page value the scatter path drops)."""
        width = self.max_pages_per_seq if width is None else width
        row = np.full(width, self.num_pages, np.int32)
        pages = self._seqs[seq_id]["pages"]
        row[:len(pages)] = pages
        return row

    def check_invariants(self):
        """Refcount bookkeeping audit (tests): every page is either free
        (ref 0) or referenced exactly as many times as sequences AND the
        prefix cache hold it, and the free list is duplicate-free. The
        cache-hold half is what proves prefix-cache eviction can never
        free a page a live sequence holds: ``cache_release`` only
        returns a page to the free list when dropping the cache's own
        ref leaves zero — a live holder keeps it referenced."""
        counts = np.zeros(self.num_pages, np.int32)
        for s in self._seqs.values():
            for p in s["pages"]:
                counts[p] += 1
        if not np.array_equal(counts + self._cache_hold, self._ref):
            raise AssertionError(
                f"refcount drift: held={counts.tolist()} "
                f"cached={self._cache_hold.tolist()} "
                f"ref={self._ref.tolist()}")
        if np.any(self._cache_hold < 0) or np.any(self._cache_hold > 1):
            raise AssertionError(
                f"cache-hold out of range: {self._cache_hold.tolist()}")
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("duplicate pages on the free list")
        if any(self._ref[p] != 0 for p in free):
            raise AssertionError("referenced page on the free list")
        if len(free) + int((self._ref > 0).sum()) != self.num_pages:
            raise AssertionError("leaked page: neither free nor referenced")
        if self.state_rows:
            held = [s["row"] for s in self._seqs.values()]
            if sorted(held + self._free_rows) != list(range(self.state_rows)):
                raise AssertionError(
                    f"state rows drift: held={held} free={self._free_rows}")

    # -- lifecycle ----------------------------------------------------------

    def _take(self, n: int) -> Optional[List[int]]:
        if len(self._free) < n:
            return None
        taken = [self._free.pop() for _ in range(n)]
        for p in taken:
            self._ref[p] += 1
        return taken

    def _new_seq(self, seq_id: int, pages: List[int]):
        self._seqs[seq_id] = {"pages": pages, "len": 0}
        if self.state_rows:
            self._seqs[seq_id]["row"] = self._free_rows.pop()
            self.rows_assigned += 1

    def alloc(self, seq_id: int, n_tokens: int) -> Optional[List[int]]:
        """Create a sequence with capacity for ``n_tokens`` (its written
        length starts at 0 — ``advance`` after the KV lands). None = OOM."""
        E.enforce(seq_id not in self._seqs,
                  f"sequence {seq_id} already allocated")
        need = self.pages_for(n_tokens)
        E.enforce(need <= self.max_pages_per_seq,
                  f"{n_tokens} tokens need {need} pages > "
                  f"max_pages_per_seq {self.max_pages_per_seq}")
        if self.state_rows and not self._free_rows:
            return None
        pages = self._take(need)
        if pages is None:
            return None
        self._new_seq(seq_id, pages)
        return pages

    def alloc_prefix(self, seq_id: int, shared_pages: List[int],
                     n_tokens: int) -> Optional[List[int]]:
        """Create a sequence whose leading pages are SHARED (pure
        refcount bumps — the ``fork`` seam at admission granularity):
        ``shared_pages`` hold the committed KV of a cached prompt
        prefix; the remainder up to ``n_tokens`` capacity is taken
        fresh. The shared region is strictly shorter than the prompt
        (the cache caps matches below the last prompt token), so the
        holder's writes start at/after ``len(shared_pages)`` pages and
        a shared page is never written — CoW via ``ensure`` still
        covers any later aliasing. None = OOM, state unchanged."""
        E.enforce(seq_id not in self._seqs,
                  f"sequence {seq_id} already allocated")
        self._no_state("alloc_prefix")
        need = self.pages_for(n_tokens)
        E.enforce(need <= self.max_pages_per_seq,
                  f"{n_tokens} tokens need {need} pages > "
                  f"max_pages_per_seq {self.max_pages_per_seq}")
        E.enforce(len(shared_pages) < need,
                  f"shared prefix ({len(shared_pages)} pages) must "
                  f"leave a fresh tail page (need {need})")
        E.enforce(all(self._ref[p] > 0 for p in shared_pages),
                  "shared prefix references an unreferenced page")
        fresh = self._take(need - len(shared_pages))
        if fresh is None:
            return None
        for p in shared_pages:
            self._ref[p] += 1
        pages = list(shared_pages) + fresh
        self._seqs[seq_id] = {"pages": pages, "len": 0}
        return pages

    def cache_hold(self, page: int):
        """Pin ``page`` with the prefix cache's own ref. Only committed
        (currently referenced) pages may be cached — insertion runs at
        retirement BEFORE the sequence's ``free``."""
        E.enforce(self._ref[page] > 0,
                  f"cache_hold on unreferenced page {page}")
        E.enforce(self._cache_hold[page] == 0,
                  f"page {page} already cache-held")
        self._ref[page] += 1
        self._cache_hold[page] = 1

    def cache_release(self, page: int) -> int:
        """Drop the cache's pin on ``page``. Returns 1 if the page hit
        the free list (no live sequence held it), else 0 — eviction by
        construction never frees a live sequence's page."""
        E.enforce(self._cache_hold[page] == 1,
                  f"cache_release on unheld page {page}")
        self._cache_hold[page] = 0
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)
            return 1
        return 0

    def ensure(self, seq_id: int, total_tokens: int
               ) -> Optional[Tuple[List[int], List[Tuple[int, int]]]]:
        """Grow capacity to ``total_tokens`` and copy-on-write any SHARED
        page the upcoming writes (positions >= current len) would touch.
        Returns (new_pages, cow_pairs[(src, dst)]) — the caller must
        mirror cow_pairs onto the device pool — or None on OOM (state
        unchanged)."""
        s = self._seqs[seq_id]
        need_total = self.pages_for(total_tokens)
        if need_total > self.max_pages_per_seq:   # (no message built else)
            raise E.PreconditionNotMetError(
                f"{total_tokens} tokens need {need_total} pages > "
                f"max_pages_per_seq {self.max_pages_per_seq}")
        grow = max(0, need_total - len(s["pages"]))
        first_written = s["len"] // self.page_size
        cow_idx = [i for i in range(first_written,
                                    min(len(s["pages"]), need_total))
                   if self._ref[s["pages"][i]] > 1]
        fresh = self._take(grow + len(cow_idx))
        if fresh is None:
            return None
        new_pages, cow_dst = fresh[:grow], fresh[grow:]
        cow_pairs = []
        for i, dst in zip(cow_idx, cow_dst):
            src = s["pages"][i]
            cow_pairs.append((src, dst))
            self._ref[src] -= 1          # shared: never hits 0 here
            s["pages"][i] = dst
        s["pages"].extend(new_pages)
        return new_pages, cow_pairs

    def advance(self, seq_id: int, n_tokens: int = 1):
        """Record ``n_tokens`` written; capacity must already exist."""
        s = self._seqs[seq_id]
        new_len = s["len"] + int(n_tokens)
        if new_len > len(s["pages"]) * self.page_size:
            raise E.PreconditionNotMetError(
                f"advance past capacity: {new_len} tokens > "
                f"{len(s['pages'])} pages")
        s["len"] = new_len

    def fork(self, src_id: int, dst_id: int) -> List[int]:
        """Share src's pages with a new sequence (beam/top-k fork): pure
        refcount bumps, zero copies now; a later ``ensure`` on either
        side copy-on-writes the tail page."""
        E.enforce(dst_id not in self._seqs,
                  f"sequence {dst_id} already allocated")
        self._no_state("fork")
        s = self._seqs[src_id]
        for p in s["pages"]:
            self._ref[p] += 1
        self._seqs[dst_id] = {"pages": list(s["pages"]), "len": s["len"]}
        return list(s["pages"])

    def _no_state(self, what: str):
        """Pages are shared by refcount; a recurrent state is one row a
        sequence, and sharing a prefix or forking would need a snapshot
        of it at the shared position, which nothing takes yet."""
        E.enforce(not self.state_rows,
                  f"{what}: sequences here keep a recurrent state beside "
                  f"their pages, and there is no state snapshot to share "
                  f"or copy (pages alone can be)",
                  error=E.UnimplementedError)

    def free(self, seq_id: int):
        s = self._seqs.pop(seq_id)
        if "row" in s:
            self._free_rows.append(s["row"])
        for p in s["pages"]:
            self._ref[p] -= 1
            E.enforce(self._ref[p] >= 0, f"double free of page {p}")
            if self._ref[p] == 0:
                self._free.append(p)


class _RadixNode:
    """One page of cached prefix: ``key`` is the page's token tuple,
    path-from-root is the page-aligned prefix it completes."""
    __slots__ = ("key", "page", "children", "parent", "stamp")

    def __init__(self, key, page, parent, stamp):
        self.key = key
        self.page = page
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.parent = parent
        self.stamp = stamp


class PrefixCache:
    """Radix tree over committed, page-aligned KV prefixes (vLLM
    automatic-prefix-caching / SGLang RadixAttention shape, at page
    granularity: one node per page, edge key = that page's token ids).

    Lifecycle contract with :class:`PageAllocator`:

    - ``insert`` runs at request retirement, BEFORE the sequence's
      ``free`` — only fully committed pages enter, each pinned with
      ``cache_hold`` (one extra ref owned by the cache).
    - ``match`` returns the longest cached prefix STRICTLY shorter than
      the prompt, page-aligned — admission always prefills >= 1 tail
      token because the first sampled token needs last-position logits.
      Matched nodes' LRU stamps refresh.
    - ``evict`` drops LRU leaves whose page no live sequence holds
      (``_ref == cache_hold``); releasing a live-held page would free
      nothing, so pinned leaves are skipped — the allocator audit
      (``check_invariants``) proves no shared-page free either way.

    Two sequences producing the same token path produce the same KV
    content (position-dependent rope included: same tokens at the same
    positions), so descending an existing node on insert keeps the
    cached copy — the same cross-shape determinism the ring/paged
    parity tests already pin.
    """

    def __init__(self, alloc: PageAllocator):
        self.alloc = alloc
        self.page_size = alloc.page_size
        self.root = _RadixNode(None, None, None, 0)
        self._clock = 0
        self._nodes = 0
        self.evicted_nodes = 0

    @property
    def nodes(self) -> int:
        return self._nodes

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _key(self, tokens, i: int) -> tuple:
        ps = self.page_size
        return tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])

    def match(self, tokens) -> Tuple[int, List[int]]:
        """Longest cached page-aligned prefix of ``tokens`` capped at
        ``len(tokens) - 1``: returns (n_cached_tokens, pages). Touches
        every matched node's LRU stamp."""
        limit = (len(tokens) - 1) // self.page_size
        node, pages = self.root, []
        stamp = self._tick()
        i = 0
        while i < limit:
            child = node.children.get(self._key(tokens, i))
            if child is None:
                break
            child.stamp = stamp
            pages.append(child.page)
            node = child
            i += 1
        return i * self.page_size, pages

    def insert(self, tokens, pages: List[int]) -> int:
        """Insert the committed page-aligned prefix of ``tokens`` (KV
        in ``pages``, the retiring sequence's block row). New nodes
        take a cache hold on their page; existing nodes keep the cached
        copy. Returns nodes added."""
        n_full = min(len(tokens) // self.page_size, len(pages))
        node, added = self.root, 0
        stamp = self._tick()
        for i in range(n_full):
            key = self._key(tokens, i)
            child = node.children.get(key)
            if child is None:
                self.alloc.cache_hold(pages[i])
                child = _RadixNode(key, pages[i], node, stamp)
                node.children[key] = child
                self._nodes += 1
                added += 1
            else:
                child.stamp = stamp
            node = child
        return added

    def reclaimable(self) -> int:
        """Pages eviction could return to the free list right now:
        cache-held pages whose ONLY refs are the cache's. Admission
        counts these as headroom — they are one ``evict`` away from
        free, so the watermark must not let them jam the pool."""
        a = self.alloc
        return int(np.sum((a._cache_hold > 0)
                          & (a._ref == a._cache_hold)))

    def evict(self, n_pages: int) -> int:
        """LRU leaf eviction until ``n_pages`` landed on the free list
        or nothing evictable remains. Only leaves whose page would
        actually free are dropped (interior nodes become leaves as
        their subtrees drain, so deep reclaimable pages cascade out).
        Returns pages freed."""
        a = self.alloc
        freed = 0
        while freed < n_pages:
            best = None
            stack = [self.root]
            while stack:
                nd = stack.pop()
                for ch in nd.children.values():
                    if ch.children:
                        stack.append(ch)
                    elif a._ref[ch.page] == a._cache_hold[ch.page] \
                            and (best is None or ch.stamp < best.stamp):
                        best = ch
            if best is None:
                break
            del best.parent.children[best.key]
            self._nodes -= 1
            self.evicted_nodes += 1
            freed += a.cache_release(best.page)
        return freed


# ---------------------------------------------------------------------------
# pool tensors
# ---------------------------------------------------------------------------

def init_pool(config, num_pages: int, page_size: int, dtype=None,
              kv_quant: bool = False, state_shapes: Optional[dict] = None,
              state_rows: int = 0, pool_layout=None) -> dict:
    """Fresh page pools, one [P, kv, ps, hd] grid per layer, stacked on
    a leading layer axis into ONE buffer a half (the decode step's layer
    scan carries it whole and names a layer by index). With
    ``state_shapes`` (a recurrent family's
    ``state_shapes(config)``: leaf name -> (shape a row a layer, type))
    the cache also holds ``"state"``: each leaf ``[L, state_rows + 1,
    ...]`` of zeros, the last row owned by no sequence. With
    A shape may name its own layer count, (shape, type, layers): a
    family whose layers do not all keep that leaf. ``pool_layout``
    (a family's ``pool_layout(config)``) is the pool's (layers, heads,
    head size[, the leaves' names]) where they are not the config's: a
    latent cache is one leaf, ``("latent",)``, in place of the ``k`` and
    ``v`` halves. With
    ``kv_quant`` (FLAGS_serving_kv_quant) each pool leaf is the
    quantized pair {"q": int8 codes, "s": f32 [L, P, kv] scale
    plane} — per-page per-kv-head write-time absmax scales ride the
    SAME page axis as their codes, so every page-granular operation
    (CoW copy, fork refcount, scatter-with-drop) moves code and scale
    rows together. Zero scale = untouched page, dequantizing to 0."""
    dt = dtype if dtype is not None else config.dtype
    layers, kv_heads, head_dim, *names = pool_layout or (
        config.num_hidden_layers, config.num_key_value_heads,
        config.head_dim)
    names = names[0] if names else ("k", "v")
    shape = (layers, num_pages, kv_heads, page_size, head_dim)
    if kv_quant:
        E.enforce(names == ("k", "v"),
                  f"FLAGS_serving_kv_quant with a pool of {names}: int8 "
                  f"pages of a latent row (a scale a page, the write's "
                  f"rescale, the latent kernel's dequantized read) are not "
                  f"written", error=E.UnimplementedError)

        def leaf():
            return {"q": jnp.zeros(shape, jnp.int8),
                    "s": jnp.zeros(shape[:3], jnp.float32)}
        pool = {"k": leaf(), "v": leaf()}
    else:
        pool = {name: jnp.zeros(shape, dt) for name in names}
    if state_shapes:
        # (shape a row a layer, type[, layers that keep the leaf])
        pool["state"] = {
            name: jnp.zeros((spec[2] if len(spec) > 2 else layers,
                             state_rows + 1) + tuple(spec[0]), spec[1])
            for name, spec in state_shapes.items()}
    return pool


class PagedKVCache:
    """Pool tensors + allocator under one roof — the serving engine's
    cache object. Device state lives in ``.pool`` (replaced wholesale by
    the jitted prefill/decode calls); control state in ``.alloc``."""

    def __init__(self, config, num_pages: int, page_size: int,
                 max_pages_per_seq: int, dtype=None,
                 kv_quant: bool = False,
                 state_shapes: Optional[dict] = None, state_rows: int = 0,
                 pool_layout=None):
        self.config = config
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_pages_per_seq = int(max_pages_per_seq)
        self.kv_quant = bool(kv_quant)
        self.state_rows = int(state_rows) if state_shapes else 0
        self.pool = init_pool(config, num_pages, page_size, dtype,
                              kv_quant=self.kv_quant,
                              state_shapes=state_shapes,
                              state_rows=self.state_rows,
                              pool_layout=pool_layout)
        self.alloc = PageAllocator(num_pages, page_size, max_pages_per_seq,
                                   state_rows=self.state_rows)
        # page-row copy over EVERY leaf of the page pools: the
        # quantized pool's scale planes share the page axis (axis 1) with
        # their codes, so one tree_map mirrors CoW onto codes and scales
        # exactly — the invariant the fork/CoW scale tests pin
        self._copy1 = jax.jit(
            lambda pool, src, dst: {**pool, **jax.tree.map(
                lambda a: a.at[:, dst].set(a[:, src]), _pages(pool))},
            donate_argnums=(0,))

    def apply_cow(self, pairs):
        """Mirror allocator copy-on-write decisions onto the device pool."""
        for src, dst in pairs:
            self.pool = self._copy1(self.pool,
                                    jnp.asarray(src), jnp.asarray(dst))

    def block_tables(self, seq_ids, width: Optional[int] = None
                     ) -> np.ndarray:
        """[len(seq_ids), width] block table; None entries (empty slots)
        become all-sentinel rows."""
        width = self.max_pages_per_seq if width is None else width
        rows = np.full((len(seq_ids), width), self.num_pages, np.int32)
        # one scatter for the whole table, a row's pages into its first
        # columns: the engine builds it anew for every chunk in which a
        # slot or a page changed, while the device waits
        pages = [() if sid is None else self.alloc._seqs[sid]["pages"]
                 for sid in seq_ids]
        lens = np.fromiter(map(len, pages), np.int64, len(pages))
        E.enforce(int(lens.max(initial=0)) <= width,
                  f"a sequence holds more pages than the table's width "
                  f"{width}")
        rows[np.arange(width) < lens[:, None]] = np.fromiter(
            itertools.chain.from_iterable(pages), np.int32, int(lens.sum()))
        return rows

    def state_row_table(self, seq_ids) -> np.ndarray:
        """[len(seq_ids)] rows of recurrent state, a slot each; None
        entries (empty slots) name the last row, which nobody owns."""
        return np.asarray([self.state_rows if sid is None
                           else self.alloc.state_row(sid)
                           for sid in seq_ids], np.int32)


# ---------------------------------------------------------------------------
# data plane (pure jax; family/config static under jit)
# ---------------------------------------------------------------------------

# int8 KV code range (FLAGS_serving_kv_quant). Scales are per-page
# per-kv-head write-time absmax/127 — symmetric, round-to-nearest, the
# same shape of contract as the weight-only scheme (llama.quant_int8)
# but chosen dynamically at every page write.
_KV_QMAX = 127.0


def _kv_quantize(xf, s):
    """int8 codes of f32 values under broadcastable scales ``s``."""
    return jnp.clip(jnp.round(xf / jnp.maximum(s, 1e-10)),
                    -_KV_QMAX, _KV_QMAX).astype(jnp.int8)


@jax.named_scope("attn.kv_write")
def _kv_pool_write(pool, pages, page_rows):
    """Scatter freshly computed whole-page grids ``pages``
    [L, ..., kv, ps, hd] into a pool leaf at ``page_rows`` with the
    drop discipline — quantizing in-program when the pool is the
    {"q", "s"} pair: scales are the written pages' own absmax (over
    the ps/hd axes, per kv head), and code + scale rows land under the
    SAME drop mask, so a sentinel row drops both."""
    if isinstance(pool, dict):
        xf = pages.astype(jnp.float32)
        s = jnp.max(jnp.abs(xf), axis=(-2, -1)) / _KV_QMAX
        q = _kv_quantize(xf, s[..., None, None])
        return {"q": pool["q"].at[:, page_rows].set(q, mode="drop"),
                "s": pool["s"].at[:, page_rows].set(s, mode="drop")}
    return pool.at[:, page_rows].set(pages.astype(pool.dtype),
                                     mode="drop")


def _kv_pool_gather(pool, rows, dtype):
    """Gather page rows from a pool leaf as [*rows.shape, kv, ps, hd]
    in ``dtype`` — dequantized (f32 multiply, ONE cast: the _mm seam
    ordering) when the pool is quantized."""
    if isinstance(pool, dict):
        deq = (pool["q"][rows].astype(jnp.float32)
               * pool["s"][rows][..., None, None])
        return deq.astype(dtype)
    return pool[rows].astype(dtype)


def _kv_page_append(leaf, layer, rows, off, val, P):
    """Append one token's [B, kv, hd] values at slot ``off`` of pages
    ``rows`` of layer ``layer`` (sentinel ``P`` drops) of a QUANTIZED pool
    half, the ``{"q", "s"}`` pair — ``_kv_token_append``'s int8 arm.
    ``leaf`` is the WHOLE pool half, the layer scan's carry: the scatters
    name (layer, page) and touch nothing else, so the pool stays one
    buffer, updated in place. The whole touched page is rescaled: gather,
    dequantize, zero the not-yet-written tail slots (a reused page's
    stale codes must not inflate the scale), insert the token, requantize
    under the page's fresh absmax, and scatter codes + scale row under
    one drop mask. Committed slots re-round at most once per scale
    change — bounded by page_size writes, inside the decode-parity SQNR
    budget."""
    B, kv = val.shape[0], val.shape[1]
    kvi = jnp.arange(kv)
    ps = leaf["q"].shape[3]
    rc = jnp.clip(rows, 0, P - 1)
    page = (leaf["q"][layer, rc].astype(jnp.float32)
            * leaf["s"][layer, rc][..., None, None])       # [B, kv, ps, hd]
    keep = jnp.arange(ps)[None, None, :, None] <= off[:, None, None, None]
    page = jnp.where(keep, page, 0.0)
    page = page.at[jnp.arange(B)[:, None], kvi[None, :],
                   off[:, None]].set(val.astype(jnp.float32),
                                     unique_indices=True)
    s = jnp.max(jnp.abs(page), axis=(-2, -1)) / _KV_QMAX
    q = _kv_quantize(page, s[..., None, None])
    return {"q": leaf["q"].at[layer, rows[:, None], kvi[None, :]].set(
                q, mode="drop", unique_indices=True),
            "s": leaf["s"].at[layer, rows[:, None], kvi[None, :]].set(
                s, mode="drop", unique_indices=True)}


# Behind a ``jit`` of its own, as the decode kernels further down are: a
# Pallas kernel's trace is not cached, and every decode-chunk program of a
# cell writes the same shapes.
@jax.jit
def _kv_token_write(pool_k, pool_v, layer, rows, off, k, v):
    from ..kernels import dispatched_kv_token_write

    return dispatched_kv_token_write(pool_k, pool_v, layer, rows, off, k, v)


@jax.named_scope("attn.kv_write")
def _kv_token_append(pool_k, pool_v, layer, rows, off, k, v=None):
    """Append one token's keys and values ``k``, ``v`` [B, kv, hd] at
    slot ``off`` of pages ``rows`` of layer ``layer`` of BOTH pool halves
    — the decode-step write. A half is the WHOLE leaf ``[L, P, kv, ps,
    hd]`` (a ring leaf ``[L, rows, pages, ...]``: ``rows`` then names
    ``row * pages + page``), the layer scan's carry, and comes back in
    its own buffer: one ``kv_token_write`` (``kernels/kv_write.py``) puts
    each live slot's row into its sublane tile of both halves in place
    and touches nothing else. ``rows`` at the sentinel (the pages of a
    layer) write nothing; live slots name different pages. The int8 pair
    goes a half at a time through ``_kv_page_append``. A latent pool's one
    leaf is ``pool_k`` with ``pool_v`` and ``v`` None: (the leaf, None)."""
    if isinstance(pool_k, dict):
        P = pool_k["q"].shape[1]
        return (_kv_page_append(pool_k, layer, rows, off, k, P),
                _kv_page_append(pool_v, layer, rows, off, v, P))
    if pool_v is None:
        return _kv_token_write(pool_k, None, layer, rows, off, k, None)[0], \
            None
    return _kv_token_write(pool_k, pool_v, layer, rows, off, k, v)


@jax.named_scope("attn.proj")
def _qkv_rope(x, lp, c, cos, sin):
    """One layer's attention inputs: ln1, the q/k/v products, rope."""
    h = _rms(x, lp["ln1"], c.rms_norm_eps)
    q, k, v = _qkv_proj(h, lp, c)
    return rope_raw(q, cos, sin), rope_raw(k, cos, sin), v


@jax.named_scope("attn.proj")
def _attn_out(x, a, lp):
    """The output product of one layer's attention, and its residual."""
    return x + _mm(a.astype(x.dtype), lp["wo"])


def _block(family, x, lp, c, cos, sin, attend, mix=None):
    """One decoder block round the program's attention core: the ONE seam
    of the four programs below. ``attend(q, k, v) -> (a [B, S, heads *
    head_dim], extra)`` is the program's own (plain causal attention, the
    paged kernel behind a page append, a gather of cached pages);
    ``extra`` is whatever it has to hand on (fresh K/V, updated pool
    halves). Returns (x', attend's extra, the mixer's extra or None, what
    the block hands its caller beside them or None: a routing family's
    expert of each token).

    A family whose block is "ln1, q/k/v, rope, attention, wo, residual,
    then ``decode_mlp``" needs nothing more (llama, moe). Another gives
    ``paged_block(x, lp, config, cos, sin, attend, mix)`` and composes the
    same pieces itself; ``mix(h, lp) -> (m [B, S, D], extra)`` is then the
    program's way to the family's mixer, carrying the recurrent state."""
    compose = getattr(family, "paged_block", None)
    if compose is not None:
        out = compose(x, lp, c, cos, sin, attend, mix)
        return out if len(out) == 4 else (*out, None)
    q, k, v = _qkv_rope(x, lp, c, cos, sin)
    a, extra = attend(q, k, v)
    x = _attn_out(x, a, lp)
    return family.decode_mlp(x, lp, c), extra, None, None


def _embed(family, params, ids, c):
    scaled = getattr(family, "embed_tokens", None)
    return jnp.take(params["embed"], ids, axis=0) if scaled is None \
        else scaled(params, ids, c)


def _stream(family, x, c):
    """The layer scan's carry: the residual stream ``x``; or, where the
    family's layers hand more than that from one to the next, what its
    ``stream`` puts beside it."""
    carried = getattr(family, "stream", None)
    return x if carried is None else carried(x, c)


def _residual(x):
    """The residual stream of the layer scan's carry."""
    return x[0] if isinstance(x, tuple) else x


def _rotary_dim(c) -> int:
    """The part of a head that rotates: all of it, but for a config that
    says otherwise."""
    return getattr(c, "rotary_dim", c.head_dim)


def _layer_params(params, lp, layer):
    """One layer's weights for its block: the scan's slice ``lp`` and,
    where a family keeps weights beside ``params["layers"]``
    (``params["experts"]``), those whole with the layer's index, for a
    kernel that reads a layer where it lies (as a scan's slice XLA would
    copy it out for the kernel every step)."""
    if "experts" not in params:
        return lp
    return {**lp, "experts": params["experts"], "layer": layer}


def _logits(family, params, x, c):
    """Float32 logits of hidden states that passed the last norm."""
    scaled = getattr(family, "head_logits", None)
    return _head_logits(x, family._head(params, c)) if scaled is None \
        else scaled(params, x, c)


def _pool_shape(pool_k):
    return (pool_k["q"] if isinstance(pool_k, dict) else pool_k).shape


def _pages(cache):
    """The page leaves of a cache: the two halves, or a latent pool's
    one leaf (``init_pool``)."""
    return {k: v for k, v in cache.items() if k != "state"}


def _page_shape(cache):
    """[L, P, heads, page, width] of the cache's page leaves."""
    return _pool_shape(next(iter(_pages(cache).values())))


def _halves(cache, what: str):
    """(pool_k, pool_v) of a cache of key and value halves; a latent pool
    is refused by name."""
    E.enforce("k" in cache and "v" in cache,
              f"{what}: this cache is a latent pool ({sorted(_pages(cache))}"
              f"), one row a token for keys and values both, and {what} "
              f"over it (a gather of its rows and their decompression) is "
              f"not written", error=E.UnimplementedError)
    return cache["k"], cache["v"]


def _no_state(cache, what: str):
    E.enforce("state" not in cache,
              f"{what}: this cache holds a recurrent state a sequence, "
              f"and {what} would need a snapshot of it at the shared "
              f"position or a rollback of rejected tokens; neither is "
              f"written", error=E.UnimplementedError)


# Rows of a recurrent family's prefill group that go through the blocks at
# once. A program keeps each row's state of every layer until its last
# write (4 MiB a row a layer at Falcon-H1-34B's widths), so a group wider
# than this is taken in passes: a group of all 128 slots at once (a cold
# start, a benchmark's warm-up) would otherwise ask for 3 GiB of states and
# 2 GiB of feed-forward activations beside a full chip. The scheduler's own
# groups (1, 2, 4, 8 wide) are one pass.
_PREFILL_PASS_ROWS = 8


def _rows_a_pass(G: int) -> int:
    """The largest divisor of ``G`` that is at most ``_PREFILL_PASS_ROWS``:
    passes are equal, so that one ``lax.scan`` runs them."""
    return max(d for d in range(1, min(G, _PREFILL_PASS_ROWS) + 1)
               if G % d == 0)


def cache_prefill(family, params, ids, config, cache, page_rows, slen,
                  state_rows=None, routes=False):
    """Consume a batch of padded prompts [G, S_pad] (S_pad a page
    multiple; rows are INDEPENDENT requests): writes every covered page
    of K/V into ``page_rows`` [G, S_pad/ps] (sentinel rows drop —
    padding beyond a request's owned pages never lands; an all-sentinel
    row is a group-padding dummy) and returns (cache', logits [G, V] at
    each row's position ``slen[g]``-1). Identical layer math to the
    family's ring-buffer prefill, so greedy decode parity holds
    token-for-token. A recurrent family's state lands in ``state_rows``
    [G] as it is after ``slen[g]`` tokens, not after the padding; a
    dummy row names the row nobody owns. Rows being independent, a group
    of more than ``_PREFILL_PASS_ROWS`` rows with a state is taken in
    equal passes, one after another in the same program. With
    ``routes`` a third item comes back: what every layer's block handed
    out beside the stream ([L, G, S]: a routing family's expert of each
    token; None from a family that hands nothing)."""
    G = ids.shape[0]
    per = _rows_a_pass(G) if "state" in cache or getattr(
        family, "prefill_in_passes", False) else G
    if per < G:
        def one_pass(cache, xs):
            cache, *out = _prefill_pass(family, params, xs[0], config,
                                        cache, *xs[1:])
            return cache, tuple(out)

        cache, (logits, picks) = lax.scan(one_pass, cache, jax.tree.map(
            lambda a: a.reshape(G // per, per, *a.shape[1:]),
            (ids, page_rows, slen, state_rows)))
        logits = logits.reshape(G, -1)
        if picks is not None:   # [passes, L, per, S, ..] -> [L, G, S, ..]
            picks = jnp.moveaxis(picks, 0, 1)
            picks = picks.reshape(picks.shape[:1] + (G,) + picks.shape[3:])
    else:
        cache, logits, picks = _prefill_pass(
            family, params, ids, config, cache, page_rows, slen, state_rows)
    return (cache, logits, picks) if routes else (cache, logits)


def _prefill_pass(family, params, ids, config, cache, page_rows, slen,
                  state_rows):
    if hasattr(family, "segments"):
        return _stack_prefill(family, params, ids, config, cache, page_rows,
                              slen, state_rows)
    c = config
    G, S = ids.shape
    pool_k, pool_v = cache["k"], cache["v"]
    L, P, kv, ps, hd = _pool_shape(pool_k)
    E.enforce(S % ps == 0, f"padded prompt {S} not a multiple of "
              f"page_size {ps}")
    with jax.named_scope("embed"):
        x = _embed(family, params, ids, c)
        cos, sin = rope_tables(S, _rotary_dim(c), theta=c.rope_theta)

    from ..nn.functional.attention import sdpa_raw

    def attend(q, k, v):
        with jax.named_scope("attn.kernel"):
            return sdpa_raw(q, k, v, is_causal=True).reshape(G, S, -1), \
                (k, v)

    mix = None
    if "state" in cache:
        def mix(h, lp):
            return family.mixer_prefill(h, lp, c, slen)

    def step(carry, xs):
        lp = _layer_params(params, *xs) if "experts" in params else xs
        x, kvs, st, picks = _block(family, carry, lp, c, cos, sin, attend,
                                   mix)
        return x, (kvs, st, picks)

    x, ((ks, vs), st, picks) = lax.scan(
        step, _stream(family, x, c),
        (params["layers"], jnp.arange(L)) if "experts" in params
        else params["layers"])
    x = _residual(x)
    npad = S // ps
    with jax.named_scope("attn.kv_write"):
        # [L, G, S, kv, hd] -> [L, G, npad, kv, ps, hd] page grids
        ks = jnp.moveaxis(ks.reshape(L, G, npad, ps, kv, hd), 4, 3)
        vs = jnp.moveaxis(vs.reshape(L, G, npad, ps, kv, hd), 4, 3)
    out = {"k": _kv_pool_write(pool_k, ks, page_rows),
           "v": _kv_pool_write(pool_v, vs, page_rows)}
    if st is not None:
        with jax.named_scope("ssm.scan"):
            out["state"] = jax.tree.map(
                lambda a, n: a.at[:, state_rows].set(n.astype(a.dtype)),
                cache["state"], st)
    with jax.named_scope("head"):
        x = _rms(x, params["ln_f"], c.rms_norm_eps)
        last = jnp.take_along_axis(
            x, jnp.maximum(slen - 1, 0)[:, None, None], axis=1)[:, 0]
        logits = _logits(family, params, last, c)
    return out, logits, picks


def cache_decode_step(family, params, cache, block_tables, lengths, tokens,
                      config, state_rows=None, routes=False):
    """One incremental step over the fixed slot grid. ``tokens`` [B]
    sit at position ``lengths``-1 of their sequences (``lengths`` is the
    valid KV count INCLUDING each new token; 0 marks an inactive slot —
    its write is dropped and its logits row is garbage the caller
    masks). Returns (cache', logits [B, V]). The cache is the layer
    scan's CARRY, whole: each pool half is one buffer that the KV write
    and the kernel address by (layer, page), and a recurrent family's
    state one buffer whose rows each layer updates in place
    (``state_rows`` [B]; an inactive slot is sent to the row nobody
    owns, so its own row is untouched). Nothing is sliced out of the
    cache and nothing of its size is made, so a program that donates it
    holds it once. With ``routes`` a third item comes back, as
    ``cache_prefill``'s: [L, B, 1]."""
    if hasattr(family, "segments"):
        out = _stack_decode(family, params, cache, block_tables, lengths,
                            tokens, config, state_rows)
        return out if routes else out[:2]
    c = config
    B = tokens.shape[0]
    pool_k, pool_v = cache["k"], cache["v"]
    quant = isinstance(pool_k, dict)
    L, P, kv, ps, hd = _pool_shape(pool_k)
    n = lengths
    posw = jnp.maximum(n - 1, 0)                       # [B] write position
    with jax.named_scope("embed"):
        x = _embed(family, params, tokens, c)[:, None, :]
        # rope angles computed directly at the ragged positions
        # (identical floats to a rope_tables row: same product, same cos
        # — but a fused elementwise chain instead of two table gathers
        # per step)
        rd = _rotary_dim(c)
        inv = 1.0 / (c.rope_theta ** (
            jnp.arange(0, rd, 2, jnp.float32) / rd))
        freqs = posw.astype(jnp.float32)[:, None, None] * inv  # [B,1,rd/2]
        cos, sin = jnp.cos(freqs), jnp.sin(freqs)

    page_idx = posw // ps
    off = posw % ps
    rows = jnp.take_along_axis(block_tables, page_idx[:, None],
                               axis=1)[:, 0]
    rows = jnp.where(n > 0, rows, P)                   # inactive: drop

    from ..kernels import dispatched_paged_attention

    state = cache.get("state")
    if state is not None:
        nobody = jax.tree.leaves(state)[0].shape[1] - 1
        srows = jnp.where(n > 0, state_rows, nobody)

    def step(carry, xs):
        x, pool_k, pool_v, state = carry
        lp, layer = xs
        lp = _layer_params(params, lp, layer)

        def attend(q, k, v):
            kp, vp = _kv_token_append(pool_k, pool_v, layer, rows, off,
                                      k[:, 0], v[:, 0])
            with jax.named_scope("attn.kernel"):
                if quant:
                    a = dispatched_paged_attention(
                        q[:, 0], kp["q"], vp["q"], block_tables, n,
                        k_scales=kp["s"], v_scales=vp["s"], layer=layer)
                else:
                    a = dispatched_paged_attention(
                        q[:, 0], kp, vp, block_tables, n, layer=layer)
            return a.reshape(B, 1, -1), (kp, vp)

        mix = None
        if state is not None:
            def mix(h, lp):
                return family.mixer_decode(h, lp, c, state, layer, srows)

        x, (pool_k, pool_v), state, picks = _block(
            family, x, lp, c, cos, sin, attend, mix)
        return (x, pool_k, pool_v, state), picks

    (x, kc, vc, state), picks = lax.scan(
        step, (_stream(family, x, c), pool_k, pool_v, state),
        (params["layers"], jnp.arange(L)))
    out = {"k": kc, "v": vc}
    if state is not None:
        out["state"] = state
    with jax.named_scope("head"):
        x = _rms(_residual(x), params["ln_f"], c.rms_norm_eps)
        logits = _logits(family, params, x[:, 0, :], c)
    return (out, logits, picks) if routes else (out, logits)


# ---------------------------------------------------------------------------
# a declared stack: a scan a segment, the cache whole as the carry
# ---------------------------------------------------------------------------

def _last_position_attention(q, k, v, slen, scale):
    """``q`` [G, 1, heads, hd], each row at position ``slen`` - 1 of its
    prompt, over the prompt's keys and values ``k``, ``v`` [G, S, kv, hd]
    (grouped-query; float32 scores, the probabilities in the values' type
    as the kernels have them): [G, 1, heads, hd]."""
    G, S, kv, hd = k.shape
    qg = q.reshape(G, kv, -1, hd)
    s = jnp.einsum("gkrd,gskd->gkrs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where((jnp.arange(S) < slen[:, None])[:, None, None, :], s,
                  -jnp.inf)
    a = jnp.einsum("gkrs,gskd->gkrd", jax.nn.softmax(s, -1).astype(v.dtype),
                   v, preferred_element_type=jnp.float32)
    return a.reshape(q.shape).astype(q.dtype)


# The decode kernels behind a ``jit`` of their own: a stack calls the pool's
# kernel from two segments and the ring's from one, in each of a cell's
# decode-chunk programs, with the same shapes every time; the inner ``jit``
# is traced once a process (a Pallas kernel's own trace is not cached, and
# costs a program's first call half a second each).
@functools.partial(jax.jit, static_argnames=("scale",))
def _paged_attention(q, pool_k, pool_v, tables, lengths, layer, *, scale):
    from ..kernels import dispatched_paged_attention

    return dispatched_paged_attention(q, pool_k, pool_v, tables, lengths,
                                      scale=scale, layer=layer)


@functools.partial(jax.jit, static_argnames=("scale", "rank"))
def _mla_attention(q, pool, tables, lengths, layer, *, scale, rank):
    from ..kernels import dispatched_mla_decode

    return dispatched_mla_decode(q, pool, tables, lengths, rank=rank,
                                 scale=scale, layer=layer)


@functools.partial(jax.jit, static_argnames=("window", "scale"))
def _ring_attention(q, ring_k, ring_v, layer, rows, lengths, *, window, scale):
    from ..kernels import dispatched_ring_attention

    return dispatched_ring_attention(q, ring_k, ring_v, layer, rows, lengths,
                                     window=window, scale=scale)


# The queries of a prompt above which its attention is taken a key/value
# head at a time: the flash forward keeps beside its output a copy of the
# scaled queries and a float32 statistic a row and head whose lane padding
# makes it twice a 128-wide bf16 row, three times the queries in all (at
# Command A+'s 128 heads a 16,384-token prompt's would be 1.5 GiB, more
# than a full chip has to spare). Phi-4-mini-flash's longest prompt,
# 160 MiB of queries, stays whole.
_PROMPT_ATTN_BYTES = 192 << 20


def _prompt_attention(attend, q, k, v):
    """``attend(q, k, v)`` over whole prompts ``q`` [B, S, heads, hd],
    ``k``, ``v`` [B, S, kv, hd]: in one call, or, where the queries pass
    ``_PROMPT_ATTN_BYTES``, a key/value head and its query heads at a
    time, each output written over the queries it was made from (in the
    queries' own layout, heads and their numbers along one axis)."""
    B, S, H, hd = q.shape
    kv = k.shape[2]
    if q.size * q.dtype.itemsize <= _PROMPT_ATTN_BYTES or kv == 1:
        return attend(q, k, v)
    w = H // kv * hd                    # a key/value head's query columns

    def one(i, qa):
        a = attend(lax.dynamic_slice_in_dim(qa, i * w, w, 2).reshape(
            B, S, H // kv, hd), lax.dynamic_slice_in_dim(k, i, 1, 2),
            lax.dynamic_slice_in_dim(v, i, 1, 2))
        return lax.dynamic_update_slice_in_dim(
            qa, a.reshape(B, S, w).astype(qa.dtype), i * w, 2)

    return lax.fori_loop(0, kv, one, q.reshape(B, S, H * hd)).reshape(
        q.shape)


class _StackOps:
    """The program's side of a declared stack's blocks
    (``family.stack_block(kind, x, lp, config, i, ops)``): the cache, and
    the calls that read and write it. One object serves a whole program;
    ``_scan_stack`` hands it each scan body's carry (``cache``, ``shared``)
    before the block runs and takes them back after, so the cache rides
    every segment's scan whole and nothing of its size is sliced out of it
    or made. ``shared`` is whatever one segment hands to the later ones.

    ``prefill``: whole prompts ``[G, S]`` (``slen`` valid tokens a row,
    pages ``page_rows``, the rows of state ``rows``). Else one token a
    slot: ``lengths`` count it, ``tables`` are the slots' block tables,
    ``rows`` their rows of state (an inactive slot's: nobody's)."""

    def __init__(self, cache, *, prefill, tables, lengths, rows):
        self.cache, self.shared = dict(cache), None
        self.prefill, self.tables, self.rows = prefill, tables, rows
        self.slen = self.lengths = lengths
        self.P, _, self.ps = _page_shape(cache)[1:4]
        self.prompt_kv = {}      # prefill: the keys and values a layer wrote

    @property
    def state(self):
        return self.cache["state"]

    @state.setter
    def state(self, new):
        self.cache["state"] = new

    def write_state(self, layer, leaves):
        """Prefill: what each row's sequence keeps of layer ``layer``."""
        with jax.named_scope("ssm.scan"):
            self.state = {**self.state, **{
                k: self.state[k].at[layer, self.rows].set(
                    v.astype(self.state[k].dtype))
                for k, v in leaves.items()}}

    def attend_pages(self, q, k, v, layer, *, scale):
        """Attention over pool layer ``layer``: ``q`` [B, S, heads, hd];
        ``k``, ``v`` [B, S, kv, hd] are written to the layer's pages first,
        or None for a layer that reads what another wrote. Whole prompts
        (prefill, with keys) attend causally among themselves; a decode
        step's single positions read the pages through the paged kernel; a
        prefill's last positions (``last_only`` segments) read the prompt's
        keys and values where the layer that wrote them left them in hand
        (the same numbers as its pages hold, and no kernel to trace into
        each of a cell's prefill programs)."""
        from ..nn.functional.attention import sdpa_raw

        B, S = q.shape[:2]
        if k is not None and self.prefill:
            self.prompt_kv[layer] = (k, v)
            with jax.named_scope("attn.kernel"):
                a = _prompt_attention(functools.partial(
                    sdpa_raw, is_causal=True, scale=scale), q, k, v)
            with jax.named_scope("attn.kv_write"):
                for half, t in (("k", k), ("v", v)):
                    grid = jnp.moveaxis(t.reshape(
                        B, S // self.ps, self.ps, *t.shape[2:]), 3, 2)
                    self.cache[half] = self.cache[half].at[
                        layer, self.tables].set(
                            grid.astype(self.cache[half].dtype), mode="drop")
            return a
        if k is not None:
            at, off = self._write_position()
            self.cache["k"], self.cache["v"] = _kv_token_append(
                self.cache["k"], self.cache["v"], layer, at, off,
                k[:, 0], v[:, 0])
        with jax.named_scope("attn.kernel"):
            if layer in self.prompt_kv:
                return _last_position_attention(
                    q, *self.prompt_kv[layer], self.slen, scale)
            return _paged_attention(
                q[:, 0], self.cache["k"], self.cache["v"], self.tables,
                self.lengths, layer, scale=scale)[:, None]

    def _write_position(self):
        """A decode step's (page, row in it) a slot: an inactive slot's
        page is the sentinel, which writes nothing."""
        posw = jnp.maximum(self.lengths - 1, 0)
        at = jnp.take_along_axis(self.tables, (posw // self.ps)[:, None],
                                 axis=1)[:, 0]
        return jnp.where(self.lengths > 0, at, self.P), posw % self.ps

    def attend_latent(self, row, layer, q, k=None, v=None, *, scale,
                      rank=None):
        """Multi-head latent attention over pool layer ``layer`` of the
        latent leaf: ``row`` [B, S, width] is what each position keeps
        (``[c ; k_pe]``, padded), written to the layer's pages first. A
        prefill attends causally among the prompt's own positions with the
        family's DECOMPRESSED heads ``q``, ``k``, ``v`` [B, S, H, d]; a
        decode step's single positions attend ABSORBED: ``q`` [B, 1, H,
        width] against the rows through the latent kernel, the output
        [B, 1, H, rank] the mix of the rows' first ``rank``."""
        from ..nn.functional.attention import sdpa_raw

        pool = self.cache["latent"]
        if self.prefill:
            with jax.named_scope("attn.kernel"):
                a = sdpa_raw(q, k, v, is_causal=True, scale=scale)
            with jax.named_scope("attn.kv_write"):
                # a page more than the rows hold, at the sentinel (written
                # nowhere): with one page alone XLA turns the scatter into
                # an update of a slice and lays the WHOLE pool out again
                # for it (a 5.55 GiB copy at a prompt of one page: the v5e
                # compile of the cell's (1, 128) prefill, PR 37)
                B, S, width = row.shape
                grid = jnp.pad(row.reshape(B, S // self.ps, 1, self.ps,
                                           width),
                               ((0, 0), (0, 1), (0, 0), (0, 0), (0, 0)))
                tables = jnp.pad(self.tables, ((0, 0), (0, 1)),
                                 constant_values=self.P)
                self.cache["latent"] = pool.at[layer, tables].set(
                    grid.astype(pool.dtype), mode="drop")
            return a
        at, off = self._write_position()
        pool, _ = _kv_token_append(pool, None, layer, at, off,
                                   row[:, 0, None, :])
        self.cache["latent"] = pool
        with jax.named_scope("attn.kernel"):
            return _mla_attention(q[:, 0], pool, self.tables, self.lengths,
                                  layer, scale=scale, rank=rank)[:, None]

    def attend_ring(self, q, k, v, layer, *, scale, window):
        """Attention over the last ``window`` positions, kept a sequence
        in ring ``layer`` of the state leaves ``ring_k`` / ``ring_v``
        ([layers, rows, pages, kv, ps, hd]; position p at slot p mod the
        ring's size). Prefill attends within the prompt and writes the
        ring as the prompt's end leaves it; a decode step writes its
        token's slot and reads the ring."""
        from ..kernels import dispatched_window_flash

        rk, rv = self.state["ring_k"], self.state["ring_v"]
        pages, kv, ps, hd = rk.shape[2:]
        ring = pages * ps
        if self.prefill:
            with jax.named_scope("attn.kernel"):
                a = _prompt_attention(functools.partial(
                    dispatched_window_flash, window=window, scale=scale),
                    q, k, v)
            with jax.named_scope("attn.kv_write"):
                # slot r holds the last position before slen that is r
                # mod ring (one before the prompt's start is never valid)
                last = self.slen[:, None] - 1
                pos = last - jnp.mod(last - jnp.arange(ring)[None, :], ring)
                pos = jnp.clip(pos, 0, k.shape[1] - 1)[:, :, None, None]
                rk, rv = (r.at[layer, self.rows].set(jnp.moveaxis(
                    jnp.take_along_axis(t, pos, axis=1).reshape(
                        -1, pages, ps, kv, hd), 3, 2).astype(r.dtype))
                    for r, t in ((rk, k), (rv, v)))
            self.state = {**self.state, "ring_k": rk, "ring_v": rv}
            return a
        # the ring's pages are pages: (row, slot // ps) of the leaf seen
        # as [layers, rows * pages, ...]; an inactive slot writes nothing
        slot = jnp.mod(self.lengths - 1, ring)
        at = jnp.where(self.lengths > 0, self.rows * pages + slot // ps,
                       rk.shape[1] * pages)
        rk, rv = _kv_token_append(rk, rv, layer, at, slot % ps,
                                  k[:, 0], v[:, 0])
        self.state = {**self.state, "ring_k": rk, "ring_v": rv}
        with jax.named_scope("attn.kernel"):
            return _ring_attention(
                q[:, 0], rk, rv, layer, self.rows, self.lengths,
                window=window, scale=scale)[:, None]


def _scan_stack(family, params, config, x, ops, to_last=None):
    """``x`` through every segment of the family's declaration, in order:
    a ``lax.scan`` a segment over ``params[kind]`` with (x, the cache,
    what the segments share) as the carry; a segment of one layer is that
    layer's call. A kind that several segments declare (a pattern that
    repeats) has its layers stacked in the stack's order, and each of its
    segments takes the next ``count`` of them; the block is given a
    layer's index among its kind's. ``to_last`` (prefill) cuts ``x`` and
    what is shared down to each row's last position before the first
    ``last_only`` segment. A block may hand something out beside ``x``
    (``(x, out)``: a routing family's picks); returns (x, those of every
    such layer stacked in the stack's order, or None)."""
    outs, taken = [], {}
    for seg in family.segments(config):
        if seg.last_only and to_last is not None:
            x, ops.shared = jax.tree.map(to_last, (x, ops.shared))
            to_last = None
        first = taken.get(seg.kind, 0)
        taken[seg.kind] = first + seg.count
        layers = params[seg.kind]
        if first or jax.tree.leaves(layers)[0].shape[0] != seg.count:
            layers = jax.tree.map(lambda a: a[first:first + seg.count],
                                  layers)

        def step(carry, xs, kind=seg.kind):
            x, ops.cache, ops.shared = carry
            lp = _layer_params(params, *xs)
            x = family.stack_block(kind, x, lp, config, xs[1], ops)
            x, out = x if isinstance(x, tuple) else (x, None)
            return (x, ops.cache, ops.shared), out

        carry = (x, ops.cache, ops.shared)
        if seg.count == 1:
            carry, out = step(carry, (jax.tree.map(lambda a: a[0], layers),
                                      first))
            out = None if out is None else out[None]
        else:
            carry, out = lax.scan(step, carry, (
                layers, jnp.arange(first, first + seg.count)))
        x, ops.cache, ops.shared = carry
        if out is not None:
            outs.append(out)
    return x, (jnp.concatenate(outs) if outs else None)


def _stack_prefill(family, params, ids, config, cache, page_rows, slen,
                   state_rows):
    """``_prefill_pass`` for a declared stack. Segments marked
    ``last_only`` run on each row's position ``slen`` - 1 alone, reading
    the pages the segments before them wrote."""
    c = config
    S, ps = ids.shape[1], _page_shape(cache)[3]
    E.enforce(S % ps == 0, f"padded prompt {S} not a multiple of "
              f"page_size {ps}")
    with jax.named_scope("embed"):
        x = _embed(family, params, ids, c)
    ops = _StackOps(cache, prefill=True, tables=page_rows, lengths=slen,
                    rows=state_rows)
    at = jnp.maximum(slen - 1, 0)[:, None, None]

    def to_last(t):
        ops.prefill = False
        return jnp.take_along_axis(t, at, axis=1)

    x, picks = _scan_stack(family, params, c, x, ops, to_last)
    with jax.named_scope("head"):
        if ops.prefill:                       # no segment ran on the last
            x = to_last(x)
        logits = _logits(family, params,
                         family.final_norm(params, x[:, 0], c), c)
    return ops.cache, logits, picks


def _stack_decode(family, params, cache, block_tables, lengths, tokens,
                  config, state_rows):
    """``cache_decode_step`` for a declared stack: (cache, logits, what the
    blocks handed out or None)."""
    c = config
    with jax.named_scope("embed"):
        x = _embed(family, params, tokens, c)[:, None, :]
    if "state" in cache:
        nobody = jax.tree.leaves(cache["state"])[0].shape[1] - 1
        state_rows = jnp.where(lengths > 0, state_rows, nobody)
    ops = _StackOps(cache, prefill=False, tables=block_tables,
                    lengths=lengths, rows=state_rows)
    x, picks = _scan_stack(family, params, c, x, ops)
    with jax.named_scope("head"):
        logits = _logits(family, params,
                         family.final_norm(params, x[:, 0], c), c)
    return ops.cache, logits, picks


def paged_prefill(family, params, ids, config, pool_k, pool_v, page_rows,
                  slen):
    """``cache_prefill`` for a caller that holds the two pool halves and
    nothing else: (pool_k', pool_v', logits)."""
    cache, logits = cache_prefill(family, params, ids, config,
                                  {"k": pool_k, "v": pool_v}, page_rows,
                                  slen)
    return cache["k"], cache["v"], logits


def paged_decode_step(family, params, pool_k, pool_v, block_tables,
                      lengths, tokens, config):
    """``cache_decode_step`` over the two pool halves alone: (pool_k',
    pool_v', logits)."""
    cache, logits = cache_decode_step(
        family, params, {"k": pool_k, "v": pool_v}, block_tables, lengths,
        tokens, config)
    return cache["k"], cache["v"], logits


def cache_prefill_shared(family, params, ids, config, cache, page_rows,
                         slen, ctx_rows):
    """Tail-only prefill over a SHARED cached prefix: every row owns
    ``ctx_rows`` [G, ncp] pages of committed prefix KV (the radix
    cache's, forked by refcount — all rows share the same static
    cached length ncp*ps) and prefills only its uncached tail ``ids``
    [G, S_tail] into ``page_rows`` (sentinel drops, as in
    ``cache_prefill``). Tail queries attend the gathered prefix pages
    plus causally within the tail, with rope at the true absolute
    positions, so logits at ``slen``-1 (tail-local) are identical to a
    full prefill at position ncp*ps+slen-1. Returns (cache', logits
    [G, V]). Pages alone can be shared: a cache that holds a recurrent
    state is refused."""
    c = config
    G, S = ids.shape
    _no_state(cache, "a shared-prefix prefill")
    pool_k, pool_v = _halves(cache, "a shared-prefix prefill")
    L, P, kv, ps, hd = _pool_shape(pool_k)
    ncp = ctx_rows.shape[1]
    E.enforce(S % ps == 0, f"padded tail {S} not a multiple of "
              f"page_size {ps}")
    E.enforce(ncp >= 1, "shared prefill needs a cached prefix")
    ctx = ncp * ps
    with jax.named_scope("embed"):
        x = _embed(family, params, ids, c)
        cos, sin = rope_tables(ctx + S, c.head_dim, theta=c.rope_theta)
        cos, sin = cos[ctx:], sin[ctx:]
    # key t (prefix ++ tail token-major) visible to tail query i iff
    # t <= ctx + i: the whole prefix, causal within the tail
    mask = (jnp.arange(ctx + S)[None, :]
            <= (jnp.arange(S)[:, None] + ctx))[None, None]

    from ..nn.functional.attention import sdpa_raw

    def step(carry, xs):
        lp, kpl, vpl = xs

        def attend(q, k, v):
            with jax.named_scope("attn.kernel"):
                # cached prefix pages, token-major: [G, ncp, kv, ps, hd]
                # -> [G, ctx, kv, hd] (rope already applied when they
                # were written; quantized pools dequantize in the gather)
                ck = jnp.swapaxes(_kv_pool_gather(kpl, ctx_rows, k.dtype),
                                  2, 3).reshape(G, ctx, kv, hd)
                cv = jnp.swapaxes(_kv_pool_gather(vpl, ctx_rows, v.dtype),
                                  2, 3).reshape(G, ctx, kv, hd)
                ka = jnp.concatenate([ck, k], axis=1)
                va = jnp.concatenate([cv, v], axis=1)
                a = sdpa_raw(q, ka, va, attn_mask=mask).reshape(G, S, -1)
            return a, (k, v)

        x, kvs, _, _ = _block(family, carry, lp, c, cos, sin, attend)
        return x, kvs

    x, (ks, vs) = lax.scan(step, x, (params["layers"], pool_k, pool_v))
    npad = S // ps
    with jax.named_scope("attn.kv_write"):
        ks = jnp.moveaxis(ks.reshape(L, G, npad, ps, kv, hd), 4, 3)
        vs = jnp.moveaxis(vs.reshape(L, G, npad, ps, kv, hd), 4, 3)
    out = {"k": _kv_pool_write(pool_k, ks, page_rows),
           "v": _kv_pool_write(pool_v, vs, page_rows)}
    with jax.named_scope("head"):
        x = _rms(x, params["ln_f"], c.rms_norm_eps)
        last = jnp.take_along_axis(
            x, jnp.maximum(slen - 1, 0)[:, None, None], axis=1)[:, 0]
        logits = _logits(family, params, last, c)
    return out, logits


def cache_verify_window(family, params, tokens, config, cache,
                        block_tables, kv_len, live):
    """Speculative-decode verify: process a drafted window ``tokens``
    [B, C] sitting at positions ``kv_len``..``kv_len``+C-1 of each
    sequence in ONE forward pass — the window's KV is written into the
    block-table pages first (dropped where ``live`` is False), then
    every window query attends the sequence's full paged context plus
    causally within the window. C-fold fewer sequential model passes
    than C ``cache_decode_step`` calls; identical math per position, so
    greedy argmax over the returned logits [B, C, V] reproduces the
    sequential chunk token-for-token. The host accepts the longest
    draft-matching run and simply does not ``advance`` past it —
    rejected positions' KV is masked garbage until overwritten. A
    recurrent state cannot be un-advanced that way: a cache that holds
    one is refused. Returns (cache', logits)."""
    c = config
    B, C = tokens.shape
    _no_state(cache, "a speculative verify window")
    pool_k, pool_v = _halves(cache, "a speculative verify window")
    quant = isinstance(pool_k, dict)
    L, P, kv, ps, hd = _pool_shape(pool_k)
    maxp = block_tables.shape[1]
    pos = kv_len[:, None] + jnp.arange(C)[None, :]          # [B, C]
    with jax.named_scope("embed"):
        x = _embed(family, params, tokens, c)
        inv = 1.0 / (c.rope_theta ** (
            jnp.arange(0, c.head_dim, 2, jnp.float32) / c.head_dim))
        freqs = pos.astype(jnp.float32)[:, :, None] * inv[None, None, :]
        cos, sin = jnp.cos(freqs), jnp.sin(freqs)

    page_idx = pos // ps
    off = pos % ps
    rows = jnp.take_along_axis(block_tables, page_idx, axis=1)
    rows = jnp.where(live[:, None], rows, P)                # dead: drop
    kvi = jnp.arange(kv)
    # pool slot t (token-major over this row's block table) visible to
    # window query i iff t <= kv_len + i; slots past the allocated
    # pages gather clamped garbage and sit beyond every query's limit
    mask = jnp.arange(maxp * ps)[None, None, :] <= pos[:, :, None]

    # quantized pools rewrite the window's touched pages wholesale:
    # the window spans at most nwp consecutive pages per sequence
    # (worst case: first token at the last slot of its page)
    nwp = (C + ps - 2) // ps + 1
    wstart = kv_len // ps                                   # [B]
    wi = wstart[:, None] + jnp.arange(nwp)[None, :]         # [B, nwp]
    wrows = jnp.take_along_axis(block_tables,
                                jnp.clip(wi, 0, maxp - 1), axis=1)
    # past-the-table or dead rows: sentinel, scatter drops the page
    wrows = jnp.where((wi < maxp) & live[:, None], wrows, P)
    lpi = page_idx - wstart[:, None]                        # [B, C] local
    bi = jnp.arange(B)[:, None]

    @jax.named_scope("attn.kv_write")
    def _window_rewrite(leaf, val):
        """Gather the window's nwp pages, dequantize, zero the
        not-yet-written tail (stale codes must not inflate the
        scale), insert the window tokens, requantize each page under
        its fresh absmax, scatter codes + scale rows back under one
        drop mask."""
        rc = jnp.clip(wrows, 0, P - 1)
        page = (leaf["q"][rc].astype(jnp.float32)
                * leaf["s"][rc][..., None, None])  # [B, nwp, kv, ps, hd]
        gpos = wi[:, :, None] * ps + jnp.arange(ps)[None, None, :]
        keep = gpos <= (kv_len + C - 1)[:, None, None]      # [B, nwp, ps]
        page = jnp.where(keep[:, :, None, :, None], page, 0.0)
        page = page.at[bi[:, :, None], lpi[:, :, None],
                       kvi[None, None, :], off[:, :, None]].set(
            val.astype(jnp.float32), unique_indices=True)
        s = jnp.max(jnp.abs(page), axis=(-2, -1)) / _KV_QMAX
        q = _kv_quantize(page, s[..., None, None])
        return {"q": leaf["q"].at[wrows[:, :, None],
                                  kvi[None, None, :]].set(
                    q, mode="drop", unique_indices=True),
                "s": leaf["s"].at[wrows[:, :, None],
                                  kvi[None, None, :]].set(
                    s, mode="drop", unique_indices=True)}

    from ..nn.functional.attention import sdpa_raw

    def step(carry, xs):
        lp, kpl, vpl = xs

        def attend(q, k, v):
            if quant:
                kp = _window_rewrite(kpl, k)
                vp = _window_rewrite(vpl, v)
            else:
                with jax.named_scope("attn.kv_write"):
                    kp = kpl.at[rows[:, :, None], kvi[None, None, :],
                                off[:, :, None]].set(
                        k.astype(kpl.dtype), mode="drop",
                        unique_indices=True)
                    vp = vpl.at[rows[:, :, None], kvi[None, None, :],
                                off[:, :, None]].set(
                        v.astype(vpl.dtype), mode="drop",
                        unique_indices=True)
            with jax.named_scope("attn.kernel"):
                ck = jnp.swapaxes(
                    _kv_pool_gather(kp, block_tables, q.dtype),
                    2, 3).reshape(B, maxp * ps, kv, hd)
                cv = jnp.swapaxes(
                    _kv_pool_gather(vp, block_tables, q.dtype),
                    2, 3).reshape(B, maxp * ps, kv, hd)
                a = sdpa_raw(q, ck, cv,
                             attn_mask=mask[:, None]).reshape(B, C, -1)
            return a, (kp, vp)

        x, kvs, _, _ = _block(family, carry, lp, c, cos, sin, attend)
        return x, kvs

    x, (kc, vc) = lax.scan(step, x, (params["layers"], pool_k, pool_v))
    with jax.named_scope("head"):
        x = _rms(x, params["ln_f"], c.rms_norm_eps)
        logits = _logits(family, params, x, c)
    return {"k": kc, "v": vc}, logits
