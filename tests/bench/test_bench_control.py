"""The correctness controls at a size a CPU test can hold: a whole run of
the cell with the reference put in the program's place, computed with
float8 (e4m3) or int8 conv and dense inputs, one scale per image (one step
below the bfloat16 MXU inputs each configuration states), must come out
``correct`` false through the harness's own check.  `bench/calibrate.py`
takes the same runs on the chip at each cell's own size; `PERF.md` gives
the readings."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import calibrate, run  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_bench_faults_offline import small  # noqa: E402

SEED = 2 ** 31 + 5


@pytest.mark.parametrize("act", calibrate.CONTROLS)
@pytest.mark.parametrize("cell", ["resnet34-offline-b32",
                                  "mobilenet_v1-single_stream"])
def test_control_comes_out_not_correct(cell, act):
    w = small(cell)
    r = run.run_cell(w, SEED, 0.3, False, None,
                     wrap_step=calibrate.control_step(w, SEED, act))
    assert r["attempted"] > 0
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["max_rel_err"]["value"] > w["limits"]["max_rel_err"]
