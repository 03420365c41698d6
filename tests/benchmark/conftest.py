"""One case of test_bench_manifest.py cannot hold for a configuration that
is not BERT: `test_cell_files_found_by_name` asserts `hidden_size in (768,
1024)`, the two widths the benchmark had when it was written, before it
looks at anything else of the cell. This PR may not edit that file (a
`benchmark` PR may: PERF.md section 7 names the line), so the case of the
lfm2 cell is marked as an expected failure here, strictly (it fails by that
line and no other), and test_bench_lfm2.py::test_cell_files_found_by_name
makes every other assertion of it for the cell."""

import pytest

NOT_BERT = ("test_bench_manifest.py::test_cell_files_found_by_name"
            "[lfm2-ep8-clm-8k-packed]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(NOT_BERT):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts BERT's hidden sizes (768, 1024); the same "
                       "checks without that line: test_bench_lfm2.py"))
