"""`bench/launches.py` against the two traces recorded on one TPU v5e (4
single-stream MobileNet v1 frames, 3 offline ResNet-34 batches of 32), and
by hand."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import launches as L  # noqa: E402
from bench import trace_reduce as T  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# fixture → programs in its window
FIXTURES = {"mobilenet_v1-single_stream": 4, "resnet34-offline-b32": 3}


@pytest.fixture(scope="module", params=sorted(FIXTURES))
def recorded(request):
    events = L.load(os.path.join(DATA, request.param + ".xplane.pb"))
    return request.param, events, L.reduce(events)


def test_every_program_paired_by_run_id(recorded):
    name, events, r = recorded
    assert r["programs"] == r["paired"] == FIXTURES[name]
    launches = L.pair(events)
    assert len({x["run_id"] for x in launches}) == FIXTURES[name]
    dones = [x["done"] for x in launches]
    assert None not in dones and len(set(dones)) == len(dones)
    for x in launches:   # each Done is the host seeing its own program end
        assert x["done"][0] >= x["enqueue"][1]
    lo, hi = (b * 1e9 for b in r["offset_bounds_s"])
    assert lo <= hi
    for x in launches:
        s, e = x["program"]
        assert s + lo >= x["enqueue"][1] and e + hi <= x["done"][0]


def test_offset_bounds_exclude_the_kth_dispatch_shift():
    events = L.load(os.path.join(DATA, "mobilenet_v1-single_stream.xplane.pb"))
    lo, hi = L.reduce(events)["offset_bounds_s"]
    assert lo == pytest.approx(1.594e-3, abs=1e-6)
    assert hi == pytest.approx(2.031e-3, abs=1e-6)
    shift = T.reduce(T.load(os.path.join(
        DATA, "mobilenet_v1-single_stream.xplane.pb")))["clock_shift_s"]
    assert shift == pytest.approx(1.068e-3, abs=1e-6)
    assert not lo <= shift <= hi


def test_single_stream_frames_add_up(recorded):
    name, events, r = recorded
    if not name.endswith("single_stream"):
        assert r["frames"] == []
        assert L.launch_ms({"launches": r}) is None
        return
    assert len(r["frames"]) == FIXTURES[name]
    spans = events["spans"]
    for f, (h0, _), (_, f1) in zip(r["frames"], spans["h2d"],
                                   spans["fetch"]):
        assert f["frame"] == pytest.approx((f1 - h0) / 1e9)
        assert sum(f[k] for k in L.PARTS) == pytest.approx(f["frame"],
                                                           rel=0.02)
        assert all(f[k] > 0 for k in L.PARTS)


def _events():
    """Two frames by hand (ns): program 7 runs 40-50, its enqueue ends at
    30 and the host sees it done at 60; program 8 runs 140-150.  An enqueue
    of an unknown run_id and a program never enqueued are left out."""
    return {"programs": [(40, 50, 0, 7), (140, 150, 0, 8), (300, 310, 0, 9)],
            "enqueues": [(20, 30, 0, 7), (120, 125, 0, 8), (200, 210, 0, 5)],
            "dones": [(60, 61), (170, 171)],
            "spans": {"h2d": [(0, 5), (100, 104)],
                      "dispatch": [(6, 12), (105, 110)],
                      "fetch": [(12, 70), (110, 180)]}}


def test_pairing_bounds_and_frames_by_hand():
    ev = _events()
    launches = L.pair(ev)
    assert [(x["run_id"], x["done"]) for x in launches] == [
        (7, (60, 61)), (8, (170, 171))]
    assert L.offset_bounds(launches) == (max(30 - 40, 125 - 140),
                                         min(60 - 50, 170 - 150))
    f1, f2 = L.frames(launches, ev["spans"])
    assert f1 == {"h2d": 5, "launch": 30 - 6, "device": 10,
                  "device_wait": 60 - 30 - 10, "return": 70 - 60,
                  "frame": 70}
    assert f2["launch"] == 125 - 105 and f2["device_wait"] == 170 - 125 - 10
    assert f2["return"] == 180 - 170
    r = L.reduce(ev)
    assert r["programs"] == 3 and r["paired"] == 2


def test_dones_taken_in_queue_order():
    """Three programs queued back to back: each takes the first Done after
    its enqueue that no earlier one took."""
    ev = {"programs": [(10, 20, 0, 1), (20, 30, 0, 2), (30, 40, 0, 3)],
          "enqueues": [(0, 1, 0, 1), (2, 3, 0, 2), (4, 5, 0, 3)],
          "dones": [(21, 22), (31, 32), (41, 42)], "spans": {}}
    assert [x["done"] for x in L.pair(ev)] == [(21, 22), (31, 32), (41, 42)]
    assert L.offset_bounds(L.pair(ev)) == (max(1 - 10, 3 - 20, 5 - 30), 1)
    assert L.frames(L.pair(ev), {}) == []


def test_readers_by_hand():
    frames = [{"h2d": 3e-4, "launch": 5e-4, "device": 3e-4,
               "device_wait": 4e-4, "return": 2e-4, "frame": 1.7e-3},
              {"h2d": 3e-4, "launch": 7e-4, "device": 3e-4,
               "device_wait": 6e-4, "return": 4e-4, "frame": 2.3e-3},
              {"h2d": 3e-4, "launch": 6e-4, "device": 3e-4,
               "device_wait": 5e-4, "return": 9e-4, "frame": 2.6e-3}]
    ctx = {"launches": {"frames": frames}}
    assert L.launch_ms(ctx) == pytest.approx(0.6)
    assert L.device_wait_ms(ctx) == pytest.approx(0.5)
    assert L.return_ms(ctx) == pytest.approx(0.4)
    for read in (L.launch_ms, L.device_wait_ms, L.return_ms):
        assert read({}) is None
        assert read({"launches": {"frames": []}}) is None
