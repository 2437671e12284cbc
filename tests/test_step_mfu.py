"""The whole step's share of the roofline is reported by every cell under the
name PR 55 gave it (``step_mfu_roofline.itl``: the window's counters over the
harness's clock, naming no program of the trace), and by none under the one it
replaced."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [(w["name"], w["config"]) for w in MANIFEST["workloads"]]


def test_the_benchmark_has_nine_cells():
    assert len(CELLS) == 9


@pytest.mark.parametrize("cell,config", CELLS)
def test_the_cell_reports_the_whole_steps_share(cell, config):
    """``step_mfu_roofline.itl`` lists the cell and moves ``itl_mean_ms``;
    no metric is named ``step_roofline.itl``; the cell's family counts its
    prefill programs (``prefill_work``) beside its waves (``step_mix``), so
    the share is of all the useful work and not a floor; and the reader is
    the counters' one (``reduce.step_mfu_roofline``)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import family

    by = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert "step_roofline.itl" not in by
    share = by["step_mfu_roofline.itl"]
    assert cell in share["workloads"]
    assert (share["unit"], share["better"], share["moves"],
            share["source"]) == ("%", "higher", "itl_mean_ms",
                                 "program_counter")
    assert not os.path.exists(
        os.path.join(BENCH, "metrics", "step_roofline.py"))
    with open(os.path.join(BENCH, "metrics", "step_mfu_roofline.py")) as f:
        assert "reduce.step_mfu_roofline(ctx)" in f.read()
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    with open(os.path.join(ROOT, entry["file"])) as f:
        fam = family.load(json.load(f)["family"])
    assert callable(getattr(fam, "step_mix", None))
    assert callable(getattr(fam, "prefill_work", None))
