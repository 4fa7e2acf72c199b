"""paddle.device parity: device query/selection, streams, events.

Reference capability: python/paddle/device/__init__.py (set_device,
synchronize, Stream/Event, stream_guard) + device/cuda/.

TPU-native mapping: devices are jax devices; "gpu"/"cuda" names map to
the accelerator (TPU here); streams collapse to XLA's single ordered
stream per core — Stream/Event keep the API with record/synchronize
expressed over jax.block_until_ready (the reference semantics of
"everything issued so far is done").
"""
from __future__ import annotations

import contextlib

import jax

from ..framework.compat import CPUPlace, CUDAPlace, Place, TPUPlace

__all__ = [
    "get_all_device_type", "get_all_custom_device_type",
    "get_available_device", "get_available_custom_device",
    "get_cudnn_version", "get_device", "set_device", "is_compiled_with_cinn",
    "is_compiled_with_cuda", "is_compiled_with_custom_device",
    "is_compiled_with_distribute", "is_compiled_with_ipu",
    "is_compiled_with_rocm", "is_compiled_with_xpu", "IPUPlace", "XPUPlace",
    "Stream", "Event", "current_stream", "set_stream", "stream_guard",
    "synchronize", "cuda", "register_pjrt_plugin",
]

_current_device = None

# -- plugin devices (reference: phi/backends/custom/custom_device.cc +
# -- phi/capi/ — third-party hardware registers kernels/runtime hooks at
# -- load time). TPU-native seam: a PJRT plugin .so IS the registration
# -- unit — once registered as a jax platform, every op in this
# -- framework reaches it through jnp/lax lowering, so no per-op C hook
# -- table is needed (the PJRT C API plays the role of phi/capi).
_custom_plugins: dict = {}


def register_pjrt_plugin(device_type: str, library_path: str,
                         options=None, priority: int = 400):
    """Register a third-party PJRT plugin as a selectable device type.

    ``library_path`` points at the vendor's PJRT C-API shared library
    (the artifact every modern accelerator vendor ships). After
    registration the platform participates in jax backend discovery:
    ``set_device("<device_type>")``, sharding meshes, and every op in
    this framework work unchanged on it. Registration is idempotent per
    device_type; the library loads lazily at first backend use.
    """
    import os

    from ..core import enforce as E

    E.enforce(device_type and device_type.isidentifier(),
              f"plugin device_type must be an identifier, got "
              f"{device_type!r}", E.InvalidArgumentError)
    if device_type in _custom_plugins:
        return _custom_plugins[device_type]
    if not os.path.exists(library_path):
        raise E.NotFoundError(
            f"PJRT plugin library not found: {library_path!r}",
            hint="pass the vendor's PJRT C-API .so (see jax_plugins "
                 "packaging for the entry-point alternative)")
    from jax._src import xla_bridge as _xb

    try:
        _xb.register_plugin(device_type, library_path=str(library_path),
                            options=options, priority=priority)
    except Exception as e:
        raise E.ExternalError(
            f"PJRT plugin {library_path!r} failed to load: {e}",
            hint="the library must export GetPjrtApi (PJRT C API)") \
            from e
    _custom_plugins[device_type] = str(library_path)
    return str(library_path)


def get_all_device_type():
    kinds = {"cpu"}
    for d in jax.devices():
        kinds.add("gpu" if d.platform in ("tpu", "gpu") else d.platform)
    return sorted(kinds)


def get_all_custom_device_type():
    return sorted(_custom_plugins)


def get_available_device():
    out = []
    for d in jax.devices():
        plat = "gpu" if d.platform in ("tpu", "gpu") else d.platform
        name = f"{plat}:{d.id}"
        if name not in out:
            out.append(name)
    if "cpu" not in {n.split(":")[0] for n in out}:
        out.append("cpu")
    return out


def get_available_custom_device():
    out = []
    for t in sorted(_custom_plugins):
        try:
            for d in jax.devices(t):
                out.append(f"{t}:{d.id}")
        except RuntimeError:
            pass        # registered but not initializable on this host
    return out


def get_cudnn_version():
    return None            # no cuDNN in a TPU build


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    d = jax.devices()[0]
    plat = "gpu" if d.platform == "tpu" else d.platform
    return f"{plat}:{d.id}"


def set_device(device):
    global _current_device
    if isinstance(device, Place):
        device = ("cpu" if isinstance(device, CPUPlace)
                  else f"gpu:{device.get_device_id()}")
    _current_device = str(device)
    return _current_device


def is_compiled_with_cinn():
    return False


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(device_type):
    return device_type in _custom_plugins


def is_compiled_with_distribute():
    return True            # XLA collectives are always in the build


class IPUPlace(Place):
    _kind = "ipu"

    def __init__(self, id: int = 0):
        raise NotImplementedError(
            "IPU hardware is not supported by this TPU-native runtime")


class XPUPlace(Place):
    _kind = "xpu"


class Event:
    """Device event (reference: device/__init__.py Event). On XLA's
    single-stream model, record() marks the point after all issued work;
    synchronize()/query() resolve through block-until-ready."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event):
        return 0.0


class Stream:
    """Device stream (reference: device/__init__.py Stream). XLA runs one
    ordered stream per core; this handle preserves the API."""

    def __init__(self, device=None, priority=2, blocking=False):
        self.device = device

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


_default_stream = Stream()
_stream_stack = []


def current_stream(device=None):
    return _stream_stack[-1] if _stream_stack else _default_stream


def set_stream(stream):
    global _default_stream
    prev = current_stream()
    _default_stream = stream
    return prev


@contextlib.contextmanager
def stream_guard(stream):
    _stream_stack.append(stream)
    try:
        yield
    finally:
        _stream_stack.pop()


def synchronize(device=None):
    """Block until all issued device work completes (reference
    semantics; XLA: wait on a trivially-committed computation)."""
    try:
        import jax.numpy as jnp

        jax.block_until_ready(jnp.zeros(()))
    except Exception:
        pass


from . import cuda  # noqa: E402,F401
from . import memory  # noqa: E402,F401
