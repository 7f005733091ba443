"""The plain reference of `bench/reference/` against the program's blockwise
conv path at a small width on the CPU, and the harness's refusal to measure
without a chip."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run  # noqa: E402
from bench.reference import common  # noqa: E402


def _small(net: str) -> dict:
    with open(os.path.join(ROOT, "bench", "configs", f"{net}-224.json")) as f:
        cfg = json.load(f)
    return dict(cfg, image_size=32, width_mult=0.25, n_classes=10,
                head_in=int(cfg["head_in"] * 0.25))


@pytest.mark.parametrize("net", ["resnet34", "mobilenet_v1"])
def test_reference_matches_blockwise_path(net):
    from repro.models.cnn import make_cnn
    from repro.serving.quantize import quantize_cnn_params
    cfg = _small(net)
    kp, kx, kb = jax.random.split(common.seed_key(2 ** 31 + 7), 3)
    params, apply = make_cnn(net, kp, n_classes=cfg["n_classes"],
                             width_mult=cfg["width_mult"],
                             conv_impl="blockwise")
    qparams = quantize_cnn_params(
        common.fill_biases(params, kb, cfg["bias_std"]))
    x = jax.random.normal(kx, (3, 32, 32, 3), jnp.float32)
    got = np.asarray(jax.jit(apply)(qparams, x))
    want = run.reference_logits(cfg, kp, kb, {0: x})[0]
    assert got.shape == want.shape == (3, 10)
    rel = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert rel < 1e-4, rel
    # the biases are live: dropping them moves the logits
    assert np.max(np.abs(np.asarray(params["head"]["b"]))) == 0
    assert np.max(np.abs(np.asarray(qparams["head"]["b"]))) > 0


def test_seed_key_keeps_all_64_bits():
    assert np.array_equal(common.seed_key(5), jax.random.PRNGKey(5))
    assert not np.array_equal(common.seed_key(2 ** 32 + 5),
                              common.seed_key(5))
    with pytest.raises(ValueError):
        common.seed_key(-1)


def test_log_quantize_is_the_base_sqrt2_grid():
    w = jnp.array([[0.0, -1.0], [0.5, 0.7], [2 ** -40, 0.35]], jnp.float32)
    q = np.asarray(common.log_quantize(w, bits=6, frac_bits=1))
    scale = np.array([0.5, 1.0])
    code = np.round(2 * np.log2(np.maximum(np.abs(np.asarray(w)) / scale,
                                           1e-38)))
    want = np.sign(w) * 2.0 ** (np.clip(code, -62, 0) / 2) * scale
    assert np.allclose(q, np.where(np.asarray(w) == 0, 0, want))
    assert q[0, 0] == 0 and q[1, 0] == 0.5 and q[0, 1] == -1.0


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "resnet34-offline-b32", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
