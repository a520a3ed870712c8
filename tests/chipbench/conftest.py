"""Eight tests of earlier PRs pin `BENCHMARK.json`'s lists as their PR left
them: "the cell is the last of its list", "the metric is the last entry",
a list compared letter for letter. Each was true when written and is
outdated by the next PR that appends a cell or a metric, which is the only
way a PR may add one; PR 35 appended two cells and four metrics. Their files
belong to the benchmark, so only a `benchmark` PR may edit them (PERF.md §7
asks for it). Until then they are expected to fail, strictly: the PR that
repairs a file takes its lines out of here. `test_benchmark_lists.py`
keeps what they held in the form that stays true under appending.
"""

import pytest

PINNED_TO_AN_EARLIER_BENCHMARK_JSON = {
    # PR 33: fabric9976_ssw.metric_flaps is the last cell of every older list,
    # and the five metrics' lists are exactly PR 33's
    "test_fabric_ssw.py::test_the_cell_reports_the_warm_paths_metrics_and_the_five_new_ones",
    "test_fabric_ssw.py::test_entry_and_file_read_the_programs_gauge_or_counter[invalidation_rounds_per_event]",
    "test_fabric_ssw.py::test_entry_and_file_read_the_programs_gauge_or_counter[solve_d2h_bytes_per_event]",
    "test_fabric_ssw.py::test_entry_and_file_read_the_programs_gauge_or_counter[solve_h2d_bytes_per_event]",
    "test_fabric_ssw.py::test_entry_and_file_read_the_programs_gauge_or_counter[solve_rows]",
    "test_fabric_ssw.py::test_entry_and_file_read_the_programs_gauge_or_counter[solve_rows_padded]",
    # PR 30: full_build_ms.avg lists fabric9976.own_link_flaps alone
    "test_counter_syncs.py::test_entries_and_files_read_the_programs_counter_and_histogram",
    # PR 34: graph_links_patched_per_event is the last per-layer entry
    "test_graph_links_patched.py::test_entry_and_file_read_the_programs_counter_per_event",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid.split("tests/chipbench/")[-1] in PINNED_TO_AN_EARLIER_BENCHMARK_JSON:
            item.add_marker(pytest.mark.xfail(
                strict=True,
                reason="pins BENCHMARK.json's lists as an earlier PR left them; "
                "PR 35 appended to them (tests/chipbench/conftest.py)",
            ))
