"""``correct`` comes out false when the timed path is broken underneath:
each test drives the rest of a run (``run.run`` past the look for a chip)
at a tiny scale on the CPU with one fault of ``faults.py`` planted."""
import pytest

import faults
import run
from cells import FOREST, NAMES
from spec import load_cell

SEED = 2**31 + 77

# the faults every cell can have, and the number that has to catch each
CAUGHT_BY = {"unchanged": "core_mismatch",
             "half_batch": "incidence_mismatch",
             "core_altered": "core_mismatch"}


def run_cell(root, name):
    cell = load_cell(str(root), name)
    return run.run(cell, SEED, 0.5, False, require_tpu=False)


@pytest.mark.parametrize("name", NAMES)
def test_sound_run_is_correct(tiny_root, name):
    result = run_cell(tiny_root, name)
    assert result["correct"], result["checks"]
    assert all(c["value"] == 0 for c in result["checks"].values())


@pytest.mark.parametrize("fault,name,caught_by", [
    (f, n, c) for f, c in CAUGHT_BY.items() for n in NAMES] + [
    ("label_altered", n, "partition_mismatch") for n in FOREST])
def test_fault_is_not_correct(tiny_root, fault, name, caught_by):
    with faults.planted(fault):
        result = run_cell(tiny_root, name)
    assert not result["correct"]
    assert result["checks"][caught_by]["value"] > 0
