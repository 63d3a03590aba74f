"""The control: the program's own approximate peel (``method='approx'``,
delta 0.1) in place of the exact one the configurations state.  It has to
come out not correct; on the chip it was read at each cell's own size
(``readings.py --control``, PERF.md)."""
import pytest

import run
from cells import NAMES
from spec import load_cell


@pytest.mark.parametrize("name", NAMES)
def test_approximate_peel_is_not_correct(tiny_root, name):
    cell = load_cell(str(tiny_root), name)
    cell.config["request"].update(method="approx", delta=0.1)
    result = run.run(cell, 2**31 + 5, 0.5, False, require_tpu=False)
    assert not result["correct"]
    assert result["checks"]["core_mismatch"]["value"] > 0
