"""What a serving driver needs: weights, the reference check, the
engine at the configuration's sizes, the warm-up of every shape the mix
can produce, and the stamps a streaming client would take.

The engine is driven through ``submit`` and ``step`` only, with every
policy and flag at the program's default and its monitor off. Stamps are
taken from outside, after each ``step()`` returns: that is when a client
could first read the tokens the step made.
"""
from __future__ import annotations

import time

import numpy as np

from . import check
from .manifest import build_config, seeded_params
from .session import say, span


def pow2_up_to(n: int) -> list:
    return [1 << i for i in range(max(1, n).bit_length()) if 1 << i <= n]


class Serving:
    def __init__(self, run, min_prompt: int, max_prompt: int):
        """``run`` is the run's context (``benchmark.run.Run``)."""
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.kernels import autotune

        conf, mix = run.conf, run.mix
        blk = conf["serve"]
        self.run = run
        self.family, self.cfg = build_config(conf, "serve")
        cfg = self.cfg
        self.params = seeded_params(self.family, cfg, run.seed)
        run.mark("weights")

        # the page size the engine would pick for itself (its own call,
        # made here because num_pages has to be given in pages)
        max_len = blk["max_len"]
        page = autotune.paged_page_size(
            blk["num_slots"], cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim, -(-max_len // 16) * 16,
            cfg.dtype)
        self.check = check.serve_check(run.arch, self.family, cfg, conf,
                                       self.params, page, run.seed)
        say(f"reference check: {self.check}")
        run.mark("reference_check")

        self.eng = ServingEngine(self.family, self.params, cfg,
                                 num_slots=blk["num_slots"],
                                 max_len=max_len, page_size=page,
                                 num_pages=blk["pool_tokens"] // page)
        self.slots = blk["num_slots"]
        self.max_req = mix["max_queue_requests"]
        self.max_tok = mix["max_queue_tokens"]
        self._queued_pad = {}            # rid -> padded prompt, in queue
        self._next_rid = 0
        self.vocab = cfg.vocab_size
        self.rejected = []
        # per request, by rid: tokens asked for, tokens a client has
        # seen, and when it saw the first
        self.want, self.n_seen, self.t_first = {}, {}, {}
        self.delivered = 0               # tokens seen by clients, total
        self.kv_token_steps = 0.0        # while tracing: see stamp()
        self.traced_decode_steps = 0
        self.traced_tokens_decoded = 0
        self._kv_seen = {}
        self.warm(min_prompt, max_prompt)
        run.mark("warm_up")

    # -- the engine's grouping rule, as it stands -----------------------

    def bucket(self, plen: int) -> int:
        return max(self.eng._bucket(int(plen)), self.eng.page_size)

    def shapes(self, min_prompt: int, max_prompt: int) -> list:
        """Every (group, bucket) the caps let the engine form."""
        ps = self.eng.page_size
        buckets = sorted({self.bucket(p) for p in
                          list(range(min_prompt, max_prompt + 1, ps))
                          + [max_prompt]})
        return [(g, s) for s in buckets
                for g in pow2_up_to(min(self.max_req, self.max_tok // s))]

    def can_submit(self, plen: int) -> bool:
        live = {r.rid for r in self.eng.queue}
        self._queued_pad = {r: s for r, s in self._queued_pad.items()
                            if r in live}
        if not self._queued_pad:
            return True
        return (len(self._queued_pad) < self.max_req
                and sum(self._queued_pad.values()) + self.bucket(plen)
                <= self.max_tok)

    def submit(self, prompt: np.ndarray, out_len: int,
               track: bool = True) -> int:
        from paddle_tpu.inference import Request, RequestRejected

        rid = self._next_rid
        self._next_rid += 1
        with span("bench.submit"):
            try:
                self.eng.submit(Request(rid=rid, prompt=prompt,
                                        max_new_tokens=int(out_len)))
            except RequestRejected as e:
                self.rejected.append((rid, str(e)))
                return rid
        self._queued_pad[rid] = self.bucket(len(prompt))
        if track:
            self.want[rid] = int(out_len)
        return rid

    # -- warm-up: every shape the window can use, and no other ----------

    def warm(self, min_prompt: int, max_prompt: int) -> None:
        eng = self.eng
        shapes = self.shapes(min_prompt, max_prompt)
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        for g, s in shapes:
            for _ in range(g):
                self.submit(rng.integers(0, self.vocab, s, dtype=np.int32),
                            1, track=False)
            while eng.step():
                pass
        # both decode-chunk programs, in two steps: every slot filled at
        # once by one group of the smallest bucket (a prefill shape of its
        # own, past the caps, used here alone), with answers just long
        # enough for one turbo chunk on the full grid and one plain chunk
        s = shapes[0][1]
        new = 1 + eng.turbo_chunk + eng.decode_chunk
        for _ in range(self.slots):
            self.submit(rng.integers(0, self.vocab, s, dtype=np.int32),
                        new, track=False)
        chunks = set()
        while True:
            before = eng.stats.decode_steps
            busy = eng.step()
            chunks.add(eng.stats.decode_steps - before)
            if not busy:
                break
        eng.outputs.clear()
        self.warmed = {"prefill_shapes": len(shapes),
                       "decode_chunks": sorted(chunks - {0}),
                       "seconds": time.perf_counter() - t0}
        say(f"warm-up: {self.warmed}; prefill programs "
            f"{len(eng._prefill_fns)}, pages in use after it "
            f"{eng.cache.alloc.used_pages}")

    # -- one scheduler step, stamped from outside ------------------------

    def step(self, clock) -> bool:
        tracing = self.run.tracer.tracing
        steps0 = self.eng.stats.decode_steps
        with span("bench.engine_step"):
            busy = self.eng.step()
        t = clock()
        if tracing:
            self.traced_decode_steps += self.eng.stats.decode_steps - steps0
        with span("bench.stamp"):
            self.stamp(t, tracing)
        return busy

    def stamp(self, t: float, tracing: bool) -> None:
        for s in self.eng.slots:
            if s is None:
                continue
            rid, n, kv = s.req.rid, len(s.tokens), s.kv_len
            # decode steps this slot took in the step just made: its cache
            # grew by one each (after prefill it holds the prompt alone)
            e = kv - self._kv_seen.get(rid, kv - n + 1)
            self._kv_seen[rid] = kv
            if tracing:
                # what the paged kernel had to read for it: kv-e+1 .. kv
                self.kv_token_steps += e * kv - e * (e - 1) / 2
                self.traced_tokens_decoded += e
            if rid not in self.want:
                continue
            seen = self.n_seen.get(rid, 0)
            self.t_first.setdefault(rid, t)
            if n > seen:
                self.n_seen[rid] = n
                self.delivered += n - seen

    def finished(self, rid: int) -> bool:
        return self.n_seen.get(rid, 0) >= self.want[rid]

    def verdict(self, rids) -> tuple:
        """(attempted, failed): a request is served when it ended
        ``completed`` with exactly the tokens it asked for, all inside
        the vocabulary. One that is done and still in its slot is read
        from the slot."""
        in_slot = {s.req.rid: s for s in self.eng.slots if s is not None}
        failed = 0
        for rid in rids:
            out, slot = self.eng.outputs.get(rid), in_slot.get(rid)
            if out is not None:
                toks = np.asarray(out.tokens)
                ok = (out.finish_reason == "completed"
                      and len(toks) == self.want[rid])
            elif slot is not None and slot.done:
                toks = np.asarray(slot.tokens)
                ok = len(toks) == self.want[rid]
            else:
                toks, ok = np.zeros(0, np.int64), False
            ok = ok and toks.min() >= 0 and toks.max() < self.vocab
            failed += not ok
        return len(rids), failed

    def counters(self) -> dict:
        """Every public numeric attribute of ``engine.stats`` under
        ``engine.<name>``: a counter the program adds reaches a
        ``counter`` reader with no edit here."""
        st = self.eng.stats
        out = {f"engine.{k}": v for k, v in vars(st).items()
               if not k.startswith("_") and isinstance(v, (int, float))
               and not isinstance(v, bool)}
        out["engine.slot_steps"] = st.decode_steps * self.slots
        return out

    def window_counters(self, c0: dict, c1: dict, window_s: float) -> dict:
        """Deltas of ``counters()`` over the window, and what the traced
        slice counted."""
        return {**{k: c1[k] - c0[k] for k in c0},
                "engine.peak_pages_in_use": c1["engine.peak_pages_in_use"],
                "kv_token_steps": self.kv_token_steps,
                "traced_decode_steps": self.traced_decode_steps,
                "traced_tokens_decoded": self.traced_tokens_decoded,
                "window_s": window_s}
