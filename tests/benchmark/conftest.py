"""One expected failure, kept visible: ``test_benchmark_files.py::
test_config_files_resolve`` forbids a ``reduced`` key that matches
``hidden``, meaning widths such as ``hidden_size`` -- and so also refuses
``num_hidden_layers``, the depth, which is the one cut the benchmark's
contract allows and the ``olmoe_1b_7b`` configuration makes. A
``model_config`` PR may not edit a file of the benchmark, so until a
``benchmark`` PR narrows the pattern (PERF.md section 7) that case is marked
here, strictly: once the pattern is narrowed it fails as an unexpected pass
and this file goes. ``test_benchmark_olmoe.py`` makes the check the pattern
means.
"""
import pytest

_CASE = "test_benchmark_files.py::test_config_files_resolve[olmoe_1b_7b]"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(_CASE):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="the pattern's 'hidden' also matches "
                "num_hidden_layers, a depth (see this conftest's docstring)"))
