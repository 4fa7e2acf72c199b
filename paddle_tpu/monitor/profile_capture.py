"""On-demand device profiler capture — ``/profile?seconds=N``.

The host-side spans (``monitor/trace.py``) tell you what the
*scheduler* was doing; only a real ``jax.profiler`` trace shows what
the *device* executed and when. This module is the operator-facing
seam: one bounded, exclusive, time-boxed ``start_trace``/``stop_trace``
window an HTTP request (``monitor/server.py`` ``/profile``) or a test
triggers on demand — no code change, no restart, no always-on tracing
overhead.

- **Exclusive**: one capture at a time, process-wide. A second request
  while one runs raises :class:`CaptureBusy` (the route answers HTTP
  409). A ``jax.profiler`` session someone else started (the
  ``paddle_tpu.profiler`` Profiler with device tracing) also surfaces
  as busy — two writers into XLA's tracer is undefined.
- **Bounded**: captures land in per-capture subdirectories of the
  capture root (``PADDLE_TPU_PROFILE_DIR``, default
  ``<tmp>/paddle_tpu_profiles``); only the newest
  ``PADDLE_TPU_PROFILE_KEEP`` (default 4) are kept — oldest evicted,
  so a scrape-happy operator cannot fill the disk.
- **Correlated**: ``monitor.trace.span`` / ``step_span`` enter their
  ``jax.profiler`` annotation in every session, this one included, so a
  capture holds the program's host spans on the device trace's clock.
"""
from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time
from typing import Optional

__all__ = ["CaptureBusy", "capture_sync", "capturing", "capture_root",
           "keep_captures", "list_captures"]


class CaptureBusy(RuntimeError):
    """A capture (or a foreign jax.profiler session) is already
    running — the ``/profile`` route maps this to HTTP 409."""


_MU = threading.Lock()
_ACTIVE: list = [None]        # info dict while a capture window is open

# Hard ceiling on one capture window: an operator typo'ing seconds=3600
# must not pin the profiler (and its buffer growth) for an hour.
MAX_SECONDS = 60.0


def capture_root() -> str:
    return os.environ.get(
        "PADDLE_TPU_PROFILE_DIR",
        os.path.join(tempfile.gettempdir(), "paddle_tpu_profiles"))


def keep_captures() -> int:
    try:
        return max(int(os.environ.get("PADDLE_TPU_PROFILE_KEEP", "4")), 1)
    except ValueError:
        return 4


def capturing() -> bool:
    return _ACTIVE[0] is not None


def list_captures(root: Optional[str] = None):
    """Capture subdirectories under the root, newest first."""
    root = root or capture_root()
    try:
        subs = [d for d in os.listdir(root)
                if d.startswith("cap_")
                and os.path.isdir(os.path.join(root, d))]
    except OSError:
        return []
    return sorted(subs, reverse=True)


def _evict_old(root: str) -> int:
    """Keep the newest ``keep_captures()`` capture dirs, delete the
    rest. Returns how many were evicted."""
    evicted = 0
    for d in list_captures(root)[keep_captures():]:
        shutil.rmtree(os.path.join(root, d), ignore_errors=True)
        evicted += 1
    return evicted


def _walk_files(d: str):
    out = []
    for dirpath, _dirs, files in os.walk(d):
        for f in files:
            p = os.path.join(dirpath, f)
            try:
                size = os.path.getsize(p)
            except OSError:
                size = None
            out.append({"path": os.path.relpath(p, d), "bytes": size})
    out.sort(key=lambda e: e["path"])
    return out


def capture_sync(seconds: float, base_dir: Optional[str] = None) -> dict:
    """Run one exclusive capture window: start the jax profiler into a
    fresh subdirectory, sleep ``seconds`` (clamped to
    ``(0, MAX_SECONDS]``) while the workload runs, stop, evict old
    captures. Returns ``{"dir", "seconds", "files", "evicted",
    "kept"}``. Raises :class:`CaptureBusy` when a window is already
    open or the profiler is held by someone else."""
    from . import inc as _inc
    from . import trace as _trace

    seconds = float(seconds)
    if not seconds > 0:
        raise ValueError(f"capture seconds must be > 0, got {seconds}")
    seconds = min(seconds, MAX_SECONDS)
    root = base_dir or capture_root()
    with _MU:
        if _ACTIVE[0] is not None:
            raise CaptureBusy(
                f"a capture is already running ({_ACTIVE[0]['dir']})")
        cap_dir = os.path.join(
            root, f"cap_{time.strftime('%Y%m%d_%H%M%S')}_"
                  f"{int((time.time() % 1) * 1e6):06d}")
        os.makedirs(cap_dir, exist_ok=True)
        import jax
        try:
            jax.profiler.start_trace(cap_dir)
        except Exception as e:
            shutil.rmtree(cap_dir, ignore_errors=True)
            # a foreign profiler session (Profiler(device_tracing=True))
            # already owns the tracer — same 409 as our own window
            raise CaptureBusy(
                f"jax profiler unavailable: {type(e).__name__}: {e}"
            ) from e
        info = {"dir": cap_dir, "seconds": seconds,
                "started_unix": round(time.time(), 3)}
        _ACTIVE[0] = info
    try:
        time.sleep(seconds)
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass                 # a torn stop must still release the slot
        _ACTIVE[0] = None
    evicted = _evict_old(root)
    files = _walk_files(cap_dir)
    _inc("monitor.profile.captures",
         doc="on-demand profiler capture windows completed")
    _trace.instant("profile.capture", dir=cap_dir,
                   seconds=seconds, files=len(files))
    return {"dir": cap_dir, "seconds": seconds, "files": files,
            "evicted": evicted, "kept": list_captures(root)}
