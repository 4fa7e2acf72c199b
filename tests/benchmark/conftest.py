"""One case of a parametrised test that was written for the two
configurations PR 26 had, and takes every configuration of
``BENCHMARK.json`` as its cases: ``test_present_configurations_keep_no_
state_beside_keys_and_values`` asserts ``state_bytes_per_slot == 0``, which
``falcon-h1-34b`` (PR 27) is the first to break, on purpose. A
``model_config`` PR may not edit a file the benchmark has, so the case is
marked here as expected to fail, strictly: if it ever passes, the state
went uncounted. ``test_falcon_h1_cell.py`` holds the count that replaces
it. For the next ``benchmark`` PR: give that test the configurations whose
file states no ``state_bytes_per_slot``, and delete this file."""
import pytest

STALE = ("test_present_configurations_keep_no_state_beside_keys_and_values"
         "[falcon-h1-34b]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name == STALE:
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="the test predates a configuration "
                "that keeps a recurrent state; see this file's docstring"))
