"""A whole run of the single-stream cell on the CPU at a small width, with
the timed call sound and then broken underneath (see
`test_bench_faults_offline.py`; a batch of one has no half to leave out)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_bench_faults_offline import altered, small, stale  # noqa: E402

CELL = "mobilenet_v1-single_stream"


@pytest.mark.parametrize("fault", [None, altered, stale],
                         ids=["sound", "altered", "stale"])
def test_run_is_correct_only_when_sound(fault):
    r = run.run_cell(small(CELL), 2 ** 31 + 98, 0.3, False, None,
                     wrap_step=fault)
    assert r["attempted"] > 0
    assert set(r["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                 "setup_s"}
    assert r["correct"] is (fault is None), r["checks"]
    assert (r["failed"] == 0) is (fault is None)
