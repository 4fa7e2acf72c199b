"""Test harness config.

All tests run on CPU with 8 virtual devices so mesh/SPMD tests work without
TPU hardware — the equivalent of the reference's N-local-process distributed
test strategy (SURVEY.md §4: test/legacy_test/test_dist_base.py) realized as
single-process multi-device."""
import os

# Force CPU, by the env var and by the config after import: tests must
# never claim a chip (it belongs to one process at a time).
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt
    pt.seed(2024)
    np.random.seed(2024)
    # Full-precision matmuls for numeric parity checks (production default is
    # MXU-friendly reduced precision).
    pt.set_flags({"FLAGS_default_matmul_precision": "highest"})
    yield
    pt.set_flags({"FLAGS_default_matmul_precision": "default"})
