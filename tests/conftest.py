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


# Two cases under tests/benchmark/ that a later configuration breaks on
# purpose, marked here as expected to fail, strictly (a ``model_config`` PR
# may not edit a file the benchmark has, and tests/benchmark/conftest.py is
# one; this file is outside the benchmark's paths). PR 31:
# - ``test_present_configurations_keep_no_state...[phi-4-mini-flash]``
#   asserts ``state_bytes_per_slot == 0`` of every configuration; this one
#   keeps a ring a window layer and a state a Mamba layer a sequence, as
#   ``falcon-h1-34b`` keeps its state (its case: tests/benchmark/conftest.py).
# - ``test_falcon_h1_cell.py::test_the_cell_its_metrics_and_the_metrics_it_
#   joined`` asserts that PR 27's entries are the LAST of ``BENCHMARK.json``'s
#   lists, which holds until the next PR appends its own.
# ``tests/benchmark/test_phi4flash_cell.py`` holds what replaces both (the
# counted state; both PRs' entries, in order). For the next ``benchmark`` PR:
# give the first test the configurations whose file states no
# ``state_bytes_per_slot``, let the second find its entries by name, and
# delete this hook with tests/benchmark/conftest.py.
STALE_SINCE_PR31 = (
    "test_present_configurations_keep_no_state_beside_keys_and_values"
    "[phi-4-mini-flash]",
    "test_the_cell_its_metrics_and_the_metrics_it_joined")
# PR 33, the same kinds: ``...keep_no_state...[zaya1-8b]`` (the tails
# of two convolutions a sequence), and in ``test_phi4flash_cell.py`` the two
# tests that hold PR 31's entries to be the lists' LAST
# (``test_the_cell_its_metrics_...``; ``test_benchmark_json_only_gained_
# entries``, which strips its own cell from a list's end and no later one).
# ``test_every_key_is_the_catalogs_and_nothing_is_cut`` there reads the
# LAST configuration's ``reduced`` as its own.
# ``tests/benchmark/test_zaya_cell.py`` holds what replaces them: the counted
# rows; the three PRs' entries in order; the parent's entries unchanged but
# for appended names.
STALE_SINCE_PR33 = (
    "test_present_configurations_keep_no_state_beside_keys_and_values"
    "[zaya1-8b]",
    "test_the_cell_its_metrics_and_the_metrics_it_joined",
    "test_benchmark_json_only_gained_entries",
    "test_every_key_is_the_catalogs_and_nothing_is_cut")

# PR 35 appended five per-layer metrics and no cell: the two tests of
# ``test_zaya_cell.py`` that hold PR 33's entries to be ``per_layer``'s LAST
# eight, and the list to be three longer than its parent's.
# ``tests/benchmark/test_prefill_metrics.py`` holds what replaces them, by
# name and not by position (every entry the parent had, unchanged and in
# order; the five new ones), so the next PR that appends breaks nothing.
STALE_SINCE_PR35 = (
    "test_the_cell_its_metrics_and_the_metrics_it_joined",
    "test_benchmark_json_only_gained_entries")

# The configuration command-a-plus-05-2026 came with a cell and a
# per-layer metric, the same kinds again: ``test_present_configurations_
# keep_no_state...[command-a-plus-05-2026]`` (a ring of keys and values a
# window layer a sequence), and the two tests of ``test_axk1_cell.py`` that
# hold A.X K1's entries to be the LAST of ``BENCHMARK.json``'s lists and
# the lists one entry longer than their parent's.
# ``tests/benchmark/test_command_a_cell.py`` holds what replaces them: the
# counted rings; A.X K1's entries found by name
# (``test_a_xk1s_entries_are_there_by_name``); every entry the parent had,
# unchanged, but for appended names.
STALE_SINCE_COMMAND_A = (
    "test_present_configurations_keep_no_state_beside_keys_and_values"
    "[command-a-plus-05-2026]",
    "test_the_cell_its_metrics_and_the_metrics_it_joined",
    "test_benchmark_json_only_gained_entries")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name in STALE_SINCE_PR31 and (
                "[" in item.name or "test_falcon_h1_cell" in item.nodeid):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="the test predates the configuration "
                "phi-4-mini-flash; see tests/conftest.py"))
        elif item.name in STALE_SINCE_PR33 and (
                "[" in item.name or "test_phi4flash_cell" in item.nodeid):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="the test predates the configuration "
                "zaya1-8b; see tests/conftest.py"))
        elif item.name in STALE_SINCE_PR35 \
                and "test_zaya_cell" in item.nodeid:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="the test predates PR 35's five "
                "per-layer metrics; see tests/conftest.py"))
        elif item.name in STALE_SINCE_COMMAND_A and (
                "[" in item.name or "test_axk1_cell" in item.nodeid):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="the test predates the configuration "
                "command-a-plus-05-2026; see tests/conftest.py"))
