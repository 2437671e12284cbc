"""The cell ``command_a_plus.rag`` end to end on the CPU at the
configuration's ``rehearse_cpu`` sizes, through ``benchmark/run.py`` (server,
load generator, reference, readers): ``benchmark/testdata/
check_cohere_moe.py --rehearse``.  A rehearsal proves nothing about the chip;
it holds the control flow, the final line's keys and that every listed
counter reader prints a number.  Marked ``slow`` (a server, a reference and a
load generator for most of a minute, beside tier-1's timing-sensitive tests):
``python -m pytest tests/test_cohere_moe_rehearsal.py`` runs it."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "testdata"))


@pytest.mark.slow
def test_the_new_cell_rehearses_on_the_cpu():
    import check_cohere_moe

    assert check_cohere_moe.rehearse() == 0
