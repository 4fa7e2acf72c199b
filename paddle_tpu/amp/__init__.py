"""paddle.amp parity surface (reference: python/paddle/amp/__init__.py)."""
from . import amp_lists  # noqa
from .auto_cast import (amp_decorate, amp_guard, auto_cast, black_list,  # noqa
                        current_cast_dtype_for, decorate,
                        is_auto_cast_enabled, white_list)
from .grad_scaler import AmpScaler, GradScaler, OptimizerState  # noqa


def is_float16_supported(device=None):
    """fp16 support probe (reference: amp/auto_cast.py). TPU computes
    fp16 via upcast; MXU-native half dtype is bfloat16."""
    import jax

    return jax.default_backend() in ("tpu", "gpu")


def is_bfloat16_supported(device=None):
    """bf16 is the native TPU half dtype; CPU XLA also executes it."""
    return True
