"""`bench/scopes.py` and `bench/launches.py` end to end on two windows
recorded on one TPU v5e by `bench/layers.py --max-requests <n> --fixture`:
each an ``.xplane.pb`` and the compiled program's text (4 single-stream
MobileNet v1 frames; 3 offline ResNet-34 batches of 32)."""

import gzip
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import launches as L  # noqa: E402
from bench import scopes as S  # noqa: E402
from bench import trace_reduce as T  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# fixture → (programs, conv kernels per forward, its conv_glue reading)
FIXTURES = {"mobilenet_v1-single_stream": (4, 27, 19.8230936659261),
            "resnet34-offline-b32": (3, 36, 27.069501358386223)}


@pytest.fixture(scope="module", params=sorted(FIXTURES))
def recorded(request):
    base = os.path.join(DATA, request.param)
    with gzip.open(base + ".hlo.txt.gz", "rt") as f:
        text = f.read()
    path = base + ".layers.xplane.pb"
    trace = T.load(path)
    reduced = T.reduce(trace, top=None)
    return request.param, text, trace, S.reduce(text, reduced), path


def test_every_device_op_has_a_layer(recorded):
    name, text, trace, s, _ = recorded
    smap = S.scope_map(text)
    ops = {T.op_name(h) for h in (e[2] for e in trace["devices"][0])}
    assert ops <= set(smap)
    assert all(smap[op][0] is not None for op in ops)
    assert s["named_s"] == pytest.approx(s["busy_s"])
    assert s["unmapped"] == []
    kernels = {op for op in ops if smap[op][1] == S.KERNEL}
    assert len(kernels) == FIXTURES[name][1]
    assert {smap[op][0] for op in kernels} == set(s["conv_layers"])


def test_conv_glue_from_raw_events(recorded):
    """The reading equals the glue ops' device time summed over the raw
    events (one device, shifted and clipped to the window as
    `trace_reduce.reduce` does) over the busy time."""
    name, text, trace, s, _ = recorded
    smap = S.scope_map(text)
    (t0, t1), = trace["spans"][T.WINDOW_SPAN]
    shift = T.clock_shift(trace["programs"][0],
                          trace["spans"].get("dispatch", []))
    glue = 0.0
    for start, end, hlo in trace["devices"][0]:
        layer, role = smap[T.op_name(hlo)]
        if layer in s["conv_layers"] and role != S.KERNEL:
            glue += max(0.0, min(end + shift, t1) - max(start + shift, t0))
    got = S.conv_glue({"scopes": s})
    assert got == pytest.approx(100 * glue / 1e9 / s["busy_s"])
    assert got == pytest.approx(FIXTURES[name][2], rel=1e-9)


def test_launches_on_the_recorded_windows(recorded):
    name, _, trace, _, path = recorded
    r = L.reduce(L.load(path))
    assert r["programs"] == r["paired"] == FIXTURES[name][0]
    lo, hi = r["offset_bounds_s"]
    assert lo <= hi
    shift = T.reduce(trace)["clock_shift_s"]
    assert shift < lo            # the k-th dispatch pairing shifts too little
    if name.endswith("single_stream"):
        assert len(r["frames"]) == FIXTURES[name][0]
        for f in r["frames"]:
            assert sum(f[k] for k in L.PARTS) == pytest.approx(f["frame"],
                                                               rel=0.02)
    else:
        assert r["frames"] == []
