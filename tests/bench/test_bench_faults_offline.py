"""A whole run of the offline cell on the CPU at a small width, with the
timed call sound and then broken underneath: `correct` must come out true
and then false.  The chip check is skipped; everything after it runs."""

import os
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402

CELL = "resnet34-offline-b32"


def small(cell: str) -> dict:
    w = run.resolve(run.load_spec(), cell)
    cfg = w["config"]
    w["config"] = dict(cfg, image_size=32, width_mult=0.125, n_classes=10,
                       head_in=int(cfg["head_in"] * 0.125))
    w["traffic"] = dict(w["traffic"], ring=3, warmup=3,
                        batch=min(w["traffic"]["batch"], 4))
    return w


def half_batch(step):
    """Half of the batch left out: its answers are the other half's."""
    def f(x):
        y = step(x)
        h = y.shape[0] // 2
        return jnp.concatenate([y[:h], y[:h]])
    return f


def altered(step):
    """One answer of every call altered where it is produced."""
    def f(x):
        y = step(x)
        return y.at[0].set(jnp.roll(y[0], 1))
    return f


def stale(step):
    """A step that returns its first answer, whatever it is sent."""
    first = []

    def f(x):
        y = step(x)
        if not first:
            first.append(y)
        return first[0]
    return f


@pytest.mark.parametrize("fault", [None, half_batch, altered, stale],
                         ids=["sound", "half_batch", "altered", "stale"])
def test_run_is_correct_only_when_sound(fault):
    r = run.run_cell(small(CELL), 2 ** 31 + 99, 0.3, False, None,
                     wrap_step=fault)
    assert r["attempted"] > 0
    assert set(r["metrics"]) == {"images_per_s", "setup_s"}
    assert r["correct"] is (fault is None), r["checks"]
    assert (r["failed"] == 0) is (fault is None)
