"""`bench/trace_reduce.py` against small traces recorded on one TPU v5e with
the harness's own spans and profiler options (4 single-stream MobileNet v1
requests; 3 offline ResNet-34 batches of 32; `record_trace_fixtures.py`),
checked by brute force on a 1 ns grid."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace_reduce as T  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# fixture → (requests in its window, convs per forward)
FIXTURES = {"mobilenet_v1-single_stream": (4, 27),
            "resnet34-offline-b32": (3, 36)}


@pytest.fixture(scope="module", params=sorted(FIXTURES))
def recorded(request):
    trace = T.load(os.path.join(DATA, request.param + ".xplane.pb"))
    return request.param, trace, T.reduce(trace)


def _shifted(trace):
    shift = T.clock_shift(trace["programs"][0], trace["spans"]["dispatch"])
    return [(s + shift, e + shift, h) for s, e, h in trace["devices"][0]]


def _grid(trace):
    """Busy and per-span masks over the window, one entry per ns."""
    (t0, t1), = trace["spans"][T.WINDOW_SPAN]
    t0, t1 = int(t0), int(t1)
    busy = np.zeros(t1 - t0, bool)
    for s, e, _ in _shifted(trace):
        busy[max(int(s), t0) - t0:max(min(int(e), t1) - t0, 0)] = True
    spans = {}
    for name in T.HOST_SPANS:
        m = np.zeros(t1 - t0, bool)
        for s, e in trace["spans"].get(name, ()):
            m[max(int(s), t0) - t0:max(min(int(e), t1) - t0, 0)] = True
        spans[name] = m
    return busy, spans


def test_window_and_busy_union(recorded):
    _, trace, r = recorded
    assert len(trace["devices"]) == 1
    (t0, t1), = trace["spans"][T.WINDOW_SPAN]
    assert r["window_s"] == pytest.approx((t1 - t0) / 1e9)
    busy, _ = _grid(trace)
    assert r["busy_s"] == pytest.approx(busy.sum() / 1e9, abs=2e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])


def test_idle_gaps_attributed_to_host_spans(recorded):
    name, trace, r = recorded
    busy, spans = _grid(trace)
    idle = ~busy
    got = dict(r["idle_gaps"])
    named = np.zeros_like(idle)
    for span, mask in spans.items():
        want = (idle & mask).sum() / 1e9
        assert got.get(span, 0.0) == pytest.approx(want, abs=2e-9), span
        named |= mask
    assert got.get(T.UNNAMED, 0.0) == pytest.approx(
        (idle & ~named).sum() / 1e9, abs=2e-9)
    assert sum(got.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], abs=1e-8)
    if name.endswith("single_stream"):
        # each request waits on its own transfer and fetch
        assert {"h2d", "dispatch", "fetch"} <= set(got)
    else:
        assert "drain" in got


def test_conv_event_selection(recorded):
    name, trace, r = recorded
    requests, convs = FIXTURES[name]
    assert r["n_conv_events"] == requests * convs
    ops = _shifted(trace)
    conv = [(s, e) for s, e, h in ops if T.is_conv(h)]
    assert len(conv) == requests * convs        # all inside the window
    assert all(T.opcode(h) == "custom-call" for _, _, h in ops
               if T.is_conv(h))
    assert not any(T.is_conv(h) for _, _, h in ops
                   if T.opcode(h) in ("copy", "fusion", "pad"))
    assert r["conv_s"] == pytest.approx(sum(e - s for s, e in conv) / 1e9)
    assert r["top_ops"] and len(r["top_ops"]) <= 10


def test_device_clock_aligned_to_dispatch(recorded):
    """Each forward is one program; after the shift none starts before the
    host dispatched it, and a smaller shift would break that."""
    name, trace, r = recorded
    programs, dispatch = trace["programs"][0], trace["spans"]["dispatch"]
    assert len(programs) == len(dispatch) == FIXTURES[name][0]
    shift = r["clock_shift_s"] * 1e9
    assert all(p + shift >= d[0] for p, d in zip(programs, dispatch))
    if shift > 0:
        assert any(p + shift - 1 < d[0] for p, d in zip(programs, dispatch))
    assert T.clock_shift([5, 9], [(7, 8)]) == 0.0   # unpaired: no shift


def test_hlo_parsing():
    h = ('%log_conv2d_fused_pallas.30 = f32[8,7,112,64]{3,2,1,0:T(8,128)} '
         'custom-call(f32[8,18,228,3]{3,2,1,0:T(8,128)} %bitcast.16), '
         'custom_call_target="tpu_custom_call"')
    assert T.op_name(h) == "log_conv2d_fused_pallas.30"
    assert T.opcode(h) == "custom-call" and T.is_conv(h)
    tup = ('%copy-start = (f32[4]{0:T(128)S(1)}, u32[]{:S(2)}) '
           'copy-start(f32[4]{0:T(128)} %x)')
    assert T.opcode(tup) == "copy-start" and not T.is_conv(tup)
    xla = ('%fusion.3 = f32[1,8,8,16]{3,2,1,0} fusion(f32[1,8,8,4] %p, '
           'f32[3,3,4,16] %w), kind=kOutput, calls=%fused_convolution.1')
    assert T.is_conv(xla)


def test_union_gaps_and_attribution_by_hand():
    busy = T.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 10)])
    assert busy == [(0, 3), (5, 7), (9, 10)]
    g = T.gaps(busy, 0, 12)
    assert g == [(3, 5), (7, 9), (10, 12)]
    got = T.attribute(g, {"fetch": [(2, 4)], "h2d": [(8, 11)]})
    assert got == {"fetch": 1, T.UNNAMED: 3, "h2d": 2}
