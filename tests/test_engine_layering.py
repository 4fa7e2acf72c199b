"""The serving hot path's layering, held by tests.

``PERF.md`` section 3 puts ``inference/engine.py`` (the scheduler) under
everything in ``monitor/``. Three things keep it there:

- the hot path's modules import nothing of the planes above them (by
  ``ast``: a function-level import counts);
- the jitted prefill, decode chunk, verify window and join calls stay at
  the frame depth under ``ServingEngine.step``, and at the bytes of
  Python frame stack under it, that they had when ``PERF.md`` section 6
  (PRs 24 and 29) found a first call costing 0.2-0.6 s by what lies
  above it;
- the names the benchmark reads on an engine and a live slot exist.
"""
import ast
import ctypes
import os
import sys
import threading

import jax
import numpy as np
import pytest

from paddle_tpu.inference import Request, ServingEngine
from paddle_tpu.models import llama as L

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HOT_PATH = ["inference/engine.py", "inference/paged.py", "models/llama.py",
            "models/moe.py", "models/falcon_h1.py",
            "kernels/paged_attention.py", "kernels/flash_attention.py",
            "kernels/ssm.py", "kernels/fused_ce.py", "training/guards.py"]
# what the hot path may not import; ``monitor.trace`` (span, step_span:
# the spans the cells read) is the one name of ``monitor`` it may
FORBIDDEN = ("paddle_tpu.monitor", "paddle_tpu.inference.failover",
             "paddle_tpu.distributed.introspect", "paddle_tpu.loadgen")
ALLOWED = ("paddle_tpu.monitor.trace",)


def imported_names(path: str, package: str):
    """Every absolute dotted name a module imports, a name a (module,
    imported name) pair so that ``from ..monitor import slo`` reads
    ``paddle_tpu.monitor.slo``."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom):
            base = package.split(".")
            if node.level:
                base = base[:len(base) - (node.level - 1)]
                base += node.module.split(".") if node.module else []
            else:
                base = node.module.split(".")
            for a in node.names:
                yield ".".join(base + [a.name])


def under(name: str, prefix: str) -> bool:
    return name == prefix or name.startswith(prefix + ".")


@pytest.mark.parametrize("module", HOT_PATH)
def test_hot_path_imports_point_down(module):
    package = "paddle_tpu." + os.path.dirname(module).replace("/", ".")
    names = list(imported_names(os.path.join(REPO, "paddle_tpu", module),
                                package))
    assert names, "the scan found no import at all: it is broken"
    up = sorted({n for n in names
                 if any(under(n, f) for f in FORBIDDEN)
                 and not any(under(n, a) for a in ALLOWED)})
    assert not up, f"{module} imports a plane above it: {up}"


def test_the_scan_sees_relative_and_function_level_imports(tmp_path):
    src = tmp_path / "m.py"
    src.write_text("from .. import monitor as _m\n"
                   "from ..monitor import trace, slo\n"
                   "def f():\n    from .failover import AdmissionJournal\n")
    names = set(imported_names(str(src), "paddle_tpu.inference"))
    assert names == {"paddle_tpu.monitor", "paddle_tpu.monitor.trace",
                     "paddle_tpu.monitor.slo",
                     "paddle_tpu.inference.failover.AdmissionJournal"}


# -- the frame rule ----------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    cfg = L.llama_tiny()
    return cfg, L.init_params(cfg, jax.random.PRNGKey(3))


def stack_address(frame) -> int:
    """Where ``frame`` lies on its thread's frame stack: CPython's
    ``PyFrameObject.f_frame``, the third word of the object (3.11 on)."""
    return ctypes.c_void_p.from_address(id(frame) + 24).value


def probe(seen, name, fn):
    """``fn`` behind a callable that notes how many Python frames lie
    between its caller and ``ServingEngine.step``'s frame, and how many
    bytes of frame stack between ``step``'s frame and its own: its own
    is pushed where the jitted call's first frame would be."""
    def probed(*args, **kwargs):
        here = sys._getframe(0)
        frame, depth = here.f_back, 0
        while frame is not None \
                and frame.f_code is not ServingEngine.step.__code__:
            frame, depth = frame.f_back, depth + 1
        seen.setdefault(name, set()).add(
            (depth, stack_address(here) - stack_address(frame))
            if frame is not None else None)
        return fn(*args, **kwargs)
    return probed


@pytest.fixture(scope="module")
def depths(model):
    """Frame depths of every jitted call of a served trace, with and
    without speculation (the verify window needs it; the chunk and the
    join run in both)."""
    cfg, params = model
    seen = {}

    def serve(spec):
        eng = ServingEngine(L, params, cfg, num_slots=2, max_len=64,
                            page_size=4, decode_chunk=2, spec_decode=spec)
        eng._chunk_fns = {k: probe(seen, "decode_chunk", f)
                          for k, f in eng._chunk_fns.items()}
        prefill_fn, spec_fn = eng._prefill_fn, eng._spec_fn
        eng._prefill_fn = lambda *a: probe(seen, "prefill", prefill_fn(*a))
        eng._spec_fn = lambda *a: probe(seen, "spec_verify", spec_fn(*a))
        eng._join = probe(seen, "join", eng._join)
        rng = np.random.default_rng(0)
        eng.run([Request(rid=rid, max_new_tokens=20, prompt=rng.integers(
            0, cfg.vocab_size, 5).astype(np.int32)) for rid in range(3)])

    for spec in (False, True):
        # a thread of its own: its frame stack starts empty, so no end of
        # a 16 KiB chunk falls between step's frame and the probe's,
        # however deep the test runner's own frames are
        t = threading.Thread(target=serve, args=(spec,))
        t.start()
        t.join()
    return seen


# measured on the parent of PR 29 (commit 6b56da2) with this probe: the
# prefill is called in _prefill_group under _admit, the chunk in
# _chunk_step, the verify window in _spec_step, the join in _chunk_step
# and (its compile, beside the group's prefill) in _prefill_group
@pytest.mark.parametrize("program,expected", [
    ("prefill", {2}), ("decode_chunk", {1}), ("spec_verify", {1}),
    ("join", {1, 2})])
def test_jitted_call_frame_depth(depths, program, expected):
    got = {depth for depth, _ in depths[program]}
    assert got == expected, (
        f"{program} is called {sorted(got)} frames under "
        f"ServingEngine.step; the parent called it at {sorted(expected)}. "
        "A frame above a jitted call costs its first call 0.2-0.6 s "
        "(PERF.md section 6, PR 24): keep the call inline, and put "
        "accounting beside it, not round it")


# The same parent, the same probe. PR 29's first tree kept every depth,
# had 12, 6 and 5 words fewer of locals in _prefill_group, _chunk_step
# and _spec_step, and its warm set-up on the chip was 4.6 s (11%) longer
# in the Falcon-H1 cell and 0.5 s in Mistral's: while a program is
# traced, a hot call of the tracer that straddles the end of one of
# CPython's 16 KiB frame-stack chunks maps and unmaps a chunk each time,
# and which call straddles it is decided by the bytes above (PERF.md
# section 6, PR 29: the page faults of one trace follow them exactly).
@pytest.mark.skipif(sys.version_info[:2] != (3, 12),
                    reason="frame sizes are CPython 3.12's (the chip's)")
@pytest.mark.parametrize("program,expected", [
    ("prefill", {1008}), ("decode_chunk", {680}), ("spec_verify", {576}),
    ("join", {680, 1008})])
def test_jitted_call_stack_bytes(depths, program, expected):
    got = {size for _, size in depths[program]}
    assert got == expected, (
        f"{program}'s first frame is pushed {sorted(got)} bytes above "
        f"ServingEngine.step's; the measured trees had {sorted(expected)}. "
        "A local more or fewer in step, _admit, _prefill_group, "
        "_chunk_step or _spec_step moves it by 8 bytes, and the warm "
        "set-up of a cell by seconds either way: keep the bytes, or "
        "measure both serving cells' warm setup_s on the chip and write "
        "the new numbers here (PERF.md section 6, PR 29)")


# -- what the benchmark reads ------------------------------------------------

@pytest.fixture(scope="module")
def live(model):
    cfg, params = model
    eng = ServingEngine(L, params, cfg, num_slots=2, max_len=32, page_size=4)
    eng.submit(Request(rid=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=8))
    assert eng.step()
    return eng


@pytest.mark.parametrize("read", [
    lambda e: e._bucket(5) == 8,
    lambda e: all(len(k) == 3 for k in e._prefill_fns),  # (g, s_pad, sampled)
    lambda e: len(e.queue) == 0,
    lambda e: e.slots[0].req.rid == 0 and e.slots[1] is None,
    lambda e: len(e.slots[0].tokens) == e.slots[0].kv_len - 5 + 1,
    lambda e: isinstance(e.outputs, dict),
    lambda e: e.page_size == 4 and e.cache.alloc.used_pages >= 2,
    lambda e: {"decode_steps", "tokens_decoded", "tokens_generated",
               "admitted", "completed", "preempted",
               "peak_pages_in_use"} <= set(vars(e.stats)),
    lambda e: callable(e.autoscale_payload) and e.draining is False
    and e.drain_complete is False,
    # sched.prefill_fill_pct: one prompt of 5 in a bucket of 8 (PR 35)
    lambda e: vars(e.stats)["prefill_grid_tokens"] == 8
    and e.stats.tokens_prefilled == 5,
], ids=["_bucket", "_prefill_fns", "queue", "slots.req", "slots.tokens",
        "outputs", "pages", "stats", "public", "prefill_grid"])
def test_benchmark_reads_exist(live, read):
    assert read(live)


def test_signature_and_paged_entry_points_stay():
    import inspect

    from paddle_tpu.inference import paged
    sig = inspect.signature(ServingEngine.__init__)
    assert list(sig.parameters)[1:4] == ["family", "params", "config"]
    assert {n: p.default for n, p in sig.parameters.items()
            if p.kind is p.KEYWORD_ONLY} == dict(
        num_slots=8, max_len=None, page_size=None, num_pages=None,
        decode_chunk=4, watermark=0.0, kv_dtype=None, kv_quant=None,
        priority_admission=None, tenant_inflight_cap=None, max_queue=None,
        shed_on_burn=None, slo_preemption=None, failover=None,
        prefix_cache=None, spec_decode=None)
    for name in ("init_pool", "paged_prefill", "paged_decode_step",
                 "cache_prefill", "cache_decode_step"):
        assert callable(getattr(paged, name))


def test_scheduler_asks_no_plane_whether_it_is_on():
    with open(os.path.join(REPO, "paddle_tpu", "inference", "engine.py"),
              encoding="utf-8") as f:
        src = f.read()
    assert "_monitor." not in src and "enabled()" not in src
    # one way out for every request, one hand-over a chunk
    assert src.count("def _finish(") == 1 and "_retire" not in src
    assert src.count("self._acct.chunk_done(") == 1
