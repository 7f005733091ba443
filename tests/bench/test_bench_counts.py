"""`bench/counts.py` against flops and bytes summed by hand from the
published layer tables (ResNet-34: He et al. 2016 Table 1 with this repo's
5×5 stem; MobileNet v1: Howard et al. 2017 Table 1)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import counts  # noqa: E402
from bench.peaks import peaks  # noqa: E402


def _conv(hw_out, k, cin_g, cout, hw_in, cin):
    """(flops, activation bytes) of one image through one conv, and the
    bytes of its codes and scales, by hand."""
    flops = 2 * hw_out * hw_out * cout * k * k * cin_g
    act = 4 * (hw_in * hw_in * cin + hw_out * hw_out * cout)
    return flops, act, k * k * cin_g * cout + 4 * cout


def _resnet34_by_hand():
    layers = [_conv(112, 5, 3, 64, 224, 3)]               # stem, stride 2
    hw, c = 56, 64                                         # after 2x2 pool
    for cout, blocks, stride in [(64, 3, 1), (128, 4, 2), (256, 6, 2),
                                 (512, 3, 2)]:
        for b in range(blocks):
            s = stride if b == 0 else 1
            ho = hw // s
            layers.append(_conv(ho, 3, c, cout, hw, c))
            layers.append(_conv(ho, 3, cout, cout, ho, cout))
            if s != 1 or c != cout:
                layers.append(_conv(ho, 1, c, cout, hw, c))
            hw, c = ho, cout
    return layers, 512


def _mobilenet_v1_by_hand():
    layers = [_conv(112, 3, 3, 32, 224, 3)]
    hw, c = 112, 32
    for cout, s in [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1),
                    (512, 2)] + [(512, 1)] * 5 + [(1024, 2), (1024, 1)]:
        ho = hw // s
        layers.append(_conv(ho, 3, 1, c, hw, c))            # depthwise
        layers.append(_conv(ho, 1, c, cout, ho, c))         # pointwise
        hw, c = ho, cout
    return layers, 1024


@pytest.mark.parametrize("net,by_hand", [("resnet34", _resnet34_by_hand),
                                         ("mobilenet_v1",
                                          _mobilenet_v1_by_hand)])
@pytest.mark.parametrize("batch", [1, 32])
def test_forward_counts_match_hand_sums(net, by_hand, batch):
    with open(os.path.join(ROOT, "bench", "configs", f"{net}-224.json")) as f:
        cfg = json.load(f)
    layers, head_in = by_hand()
    assert head_in == cfg["head_in"]
    recs = counts.program_conv_records(cfg, batch)
    assert len(recs) == len(layers)
    got = counts.forward_counts(recs, cfg["head_in"], cfg["n_classes"])
    # activations scale with the batch; the codes and scales are read once
    assert got["conv_flops"] == batch * sum(f for f, _, _ in layers)
    assert got["conv_bytes"] == (batch * sum(a for _, a, _ in layers)
                                 + sum(w for _, _, w in layers))
    assert got["head_flops"] == 2 * batch * head_in * 1000
    assert got["flops"] == got["conv_flops"] + got["head_flops"]


def test_published_totals():
    """ResNet-34 at 224²: 7.21 GFLOP of convs and 21.3 MB of codes per image;
    MobileNet v1: 1.135 GFLOP."""
    res, _ = _resnet34_by_hand()
    mbn, _ = _mobilenet_v1_by_hand()
    assert round(sum(f for f, _, _ in res) / 1e9, 2) == 7.21
    assert round(sum(w for _, _, w in res) / 1e6, 1) == 21.3
    assert round(sum(f for f, _, _ in mbn) / 1e9, 3) == 1.135


def test_least_seconds_takes_the_larger_bound():
    p = peaks("TPU v5 lite")
    assert counts.least_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert counts.least_seconds(0, 819e9, p) == pytest.approx(1.0)
    assert counts.least_seconds(197e12, 2 * 819e9, p) == pytest.approx(2.0)
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
