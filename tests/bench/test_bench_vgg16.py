"""VGG-16 at its published size (Simonyan & Zisserman 2015, Table 1 column
D, with the classifier as §3.2's dense-evaluation convs): the plain
reference against the program's blockwise path on the CPU, the counts
against hand sums, and the correctness controls of its cell."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import calibrate, counts, run  # noqa: E402
from bench.reference import common  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_bench_faults_offline import small  # noqa: E402

CELL = "vgg16-offline-b32"
SEED = 2 ** 31 + 11

with open(os.path.join(ROOT, "bench", "configs", "vgg16-224.json")) as f:
    CFG = json.load(f)


@pytest.mark.parametrize("img,width_mult,last_map",
                         [(32, 0.25, 1), (224, 1 / 16, 7)],
                         ids=["32px_pooled", "224px"])
def test_reference_matches_blockwise_path(img, width_mult, last_map):
    """At 32 px the last map is 1×1 and is pooled to 7×7 before FC6; at
    224 px it is 7×7 and FC6 reads it as it is."""
    from repro.models.cnn import make_cnn, trace_conv_shapes
    from repro.serving.quantize import quantize_cnn_params
    cfg = dict(CFG, image_size=img, width_mult=width_mult, n_classes=10)
    recs = trace_conv_shapes("vgg16", img=img, width_mult=width_mult,
                             n_classes=10)
    assert recs[12]["H"] // 2 == last_map
    assert (recs[13]["H"], recs[13]["W"], recs[13]["K"]) == (7, 7, 7)
    kp, kx, kb = jax.random.split(common.seed_key(SEED), 3)
    params, apply = make_cnn("vgg16", kp, n_classes=10,
                             width_mult=width_mult, conv_impl="blockwise")
    qparams = quantize_cnn_params(
        common.fill_biases(params, kb, cfg["bias_std"]))
    x = jax.random.normal(kx, (3, img, img, 3), jnp.float32)
    got = np.asarray(jax.jit(apply)(qparams, x))
    want = run.reference_logits(cfg, kp, kb, {0: x})[0]
    assert got.shape == want.shape == (3, 10)
    rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert rel < 1e-4, rel
    # the classifier's biases are live: dropping them moves the logits
    assert np.max(np.abs(np.asarray(qparams["fcs"][2]["b"]))) > 0


def _vgg16_by_hand():
    """(flops, activation bytes, code and scale bytes) of one image
    through each of the 13 convs and the three fully connected layers."""
    layers, hw, c = [], 224, 3
    for cout, pool in CFG["convs"]:
        layers.append((2 * hw * hw * cout * 9 * c,
                       4 * (hw * hw * c + hw * hw * cout),
                       9 * c * cout + 4 * cout))
        hw, c = (hw // 2 if pool else hw), cout
    assert (hw, c) == (7, 512)
    for cin, cout in [(7 * 7 * 512, 4096), (4096, 4096), (4096, 1000)]:
        act_in = 4 * cin
        layers.append((2 * cin * cout, act_in + 4 * cout,
                       cin * cout + 4 * cout))
    return layers


@pytest.mark.parametrize("batch", [1, 32])
def test_forward_counts_match_hand_sums(batch):
    layers = _vgg16_by_hand()
    recs = counts.program_conv_records(CFG, batch)
    assert len(recs) == len(layers) == 16
    got = counts.forward_counts(recs, CFG["head_in"], CFG["n_classes"])
    assert got["head_flops"] == 0
    assert got["flops"] == got["conv_flops"] == batch * sum(
        f for f, _, _ in layers)
    assert got["conv_bytes"] == (batch * sum(a for _, a, _ in layers)
                                 + sum(w for _, _, w in layers))


def test_published_totals():
    """30.94 GFLOP per image (30.69 in the convs, 0.247 in the
    classifier), 138,344,128 bytes of codes."""
    layers = _vgg16_by_hand()
    scales = 4 * (sum(c for c, _ in CFG["convs"]) + 4096 + 4096 + 1000)
    assert round(sum(f for f, _, _ in layers) / 1e9, 2) == 30.94
    assert round(sum(f for f, _, _ in layers[:13]) / 1e9, 2) == 30.69
    assert round(sum(f for f, _, _ in layers[13:]) / 1e9, 3) == 0.247
    assert sum(w for _, _, w in layers) - scales == 138_344_128


@pytest.mark.parametrize("act", calibrate.CONTROLS)
def test_control_fails_the_cell_limit(act):
    """A whole run of the cell at a small width with the reference, its
    conv and dense inputs in float8 or int8, in the program's place."""
    w = small(CELL)
    r = run.run_cell(w, SEED, 0.3, False, None,
                     wrap_step=calibrate.control_step(w, SEED, act))
    assert r["attempted"] > 0
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["max_rel_err"]["value"] > w["limits"]["max_rel_err"]
