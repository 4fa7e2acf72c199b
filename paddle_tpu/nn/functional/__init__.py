"""paddle.nn.functional parity surface (flat namespace).

Reference: python/paddle/nn/functional/__init__.py.
"""
from .activation import *  # noqa: F401,F403
from .common import *  # noqa: F401,F403
from .conv import *  # noqa: F401,F403
from .pooling import *  # noqa: F401,F403
from .norm import *  # noqa: F401,F403
from .loss import *  # noqa: F401,F403
from .attention import *  # noqa: F401,F403
from .vision import *  # noqa: F401,F403
from .extras import *  # noqa: F401,F403

from .activation import __all__ as _a
from .common import __all__ as _c
from .conv import __all__ as _cv
from .pooling import __all__ as _p
from .norm import __all__ as _n
from .loss import __all__ as _l
from .attention import __all__ as _at
from .vision import __all__ as _v
from .extras import __all__ as _x

__all__ = list(_a) + list(_c) + list(_cv) + list(_p) + list(_n) + \
    list(_l) + list(_at) + list(_v) + list(_x)


# diag_embed is also exposed here like the reference functional/__init__
from ...ops.manipulation_ext import diag_embed  # noqa: F401


def pdist(x, p=2.0, name=None):
    """Condensed pairwise distance of an [N, D] matrix: the upper
    triangle of cdist(x, x) flattened to [N*(N-1)/2] (reference:
    nn/functional/distance.py pdist)."""
    import jax.numpy as jnp

    from ...ops._op import op_fn

    @op_fn(name="pdist_op")
    def _pdist(x, *, p):
        n = x.shape[0]
        diff = x[:, None, :] - x[None, :, :]
        if p == 2.0:
            d = jnp.sqrt(jnp.maximum(jnp.sum(diff * diff, -1), 1e-24))
        elif p == float("inf"):
            d = jnp.max(jnp.abs(diff), -1)
        elif p == 0:
            d = jnp.sum((diff != 0).astype(x.dtype), -1)
        else:
            d = jnp.sum(jnp.abs(diff) ** p, -1) ** (1.0 / p)
        iu, ju = jnp.triu_indices(n, k=1)
        return d[iu, ju]

    return _pdist(x, p=float(p))


import contextlib as _ctx


@_ctx.contextmanager
def sdp_kernel(enable_math=True, enable_flash=True,
               enable_mem_efficient=True):
    """Scoped attention-backend selection (reference:
    nn/functional/flash_attention.py sdp_kernel — there it toggles the
    cuDNN/flash backends). Here flash means the Pallas kernel: disabling
    it unregisters the flash dispatcher within the scope."""
    from . import attention as _att
    prev = _att._FLASH_IMPL
    prev_seg = _att._SEGMENT_IMPL
    try:
        if not enable_flash:
            # actually remove the flash dispatcher so the scope runs the
            # XLA/math path (register(flash=False) would merely skip
            # re-installing it); the segment kernel is the same Pallas
            # family, so it toggles with it
            _att.register_flash_impl(None)
            _att.register_segment_impl(None)
        yield
    finally:
        # restore whatever was installed on entry verbatim — a
        # interpret=True registration (interpret-mode tests) or a
        # deliberately-unregistered state must survive the scope
        if not enable_flash:
            _att.register_flash_impl(prev)
            _att.register_segment_impl(prev_seg)
