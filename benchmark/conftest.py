"""Two tests under benchmark/tests assert that the repo's table is what
it was when they were written, so a table that grows fails them. The PR
that adds a cell may add files here but not edit one the benchmark has,
so until a ``benchmark`` PR restates them they are expected to fail,
strictly: the day their files are edited, this file goes. What they mean
to guard is restated for the table as it is in
tests/test_benchmark_harness.py, which tier-1 runs."""

import pytest

OUTGROWN = {
    "test_the_default_architecture_is_found_for_a_file_that_names_none":
        'asserts `"arch" not in cell.config` for EVERY cell of BENCHMARK.json; '
        "olmoe-raw's configuration names its architecture (edit: loop over the "
        "cells whose file names none)",
    "test_is_an_entry_of_the_table_for_the_four_chip_cell_only":
        'asserts that `table["per_layer"][-1]` is wire_fresh_bytes_step; new '
        "entries go at the end of the list (edit: look the entry up by name)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = OUTGROWN.get(item.name)
        if reason and "benchmark/tests/" in item.nodeid:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
