"""The paper's own workload end to end: a log-quantized CNN trained in JAX
with the accelerator's numerics, then 'deployed' onto the NeuroMAX
dataflow model for per-layer utilization/latency — i.e. software-hardware
co-design in one script.

    PYTHONPATH=src python examples/cnn_accelerator_sim.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accelerator import NETWORKS, run_network
from repro.models.cnn import cnn_loss, make_cnn


def train_quantized_cnn(steps=250):
    """Tiny SqueezeNet with logq6 fake-quant (the accelerator's numerics),
    fit on a fixed synthetic 8-class set (SGD + momentum)."""
    key = jax.random.PRNGKey(0)
    params, apply_fn = make_cnn("squeezenet", key, n_classes=8,
                                width_mult=0.25, quant="logq6")
    r = np.random.default_rng(0)
    y = np.tile(np.arange(8), 4).astype(np.int32)          # 32 samples
    x = r.normal(size=(32, 32, 32, 3)).astype(np.float32)
    # class-dependent frequency pattern (needs actual features, not bias)
    grid = np.linspace(0, 2 * np.pi, 32)
    for i, yy in enumerate(y):
        x[i, :, :, 0] += 2.0 * np.sin((yy + 1) * grid)[None, :]
    batch = {"images": jnp.asarray(x), "labels": jnp.asarray(y)}

    mom = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step_fn(p, m, b, lr):
        (loss, aux), g = jax.value_and_grad(
            lambda pp: cnn_loss(apply_fn, pp, b), has_aux=True)(p)
        gn = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, 1.0 / gn), g)
        m = jax.tree.map(lambda mm, gg: 0.9 * mm + gg, m, g)
        p = jax.tree.map(lambda pp, mm: pp - lr * mm, p, m)
        return loss, aux["acc"], p, m

    for s in range(steps):
        lr = 0.02 * (1.0 - 0.8 * s / steps)          # linear decay
        loss, acc, params, mom = step_fn(params, mom, batch, lr)
        if s % 30 == 0 or s == steps - 1:
            print(f"  step {s:3d}  loss {float(loss):.3f} "
                  f"acc {float(acc)*100:.0f}%")
    return float(loss), float(acc), params, apply_fn, batch


def serve_packed(params, apply_fn, batch):
    """Deployed numerics: pack conv weights to int8 log codes once, route
    every conv through kernels/ops.conv2d (the three-tier dispatch layer)."""
    import functools
    from repro.serving.quantize import quantize_cnn_params, quantized_fraction

    qparams = quantize_cnn_params(params)
    apply_q = functools.partial(apply_fn, conv_impl="auto")  # pallas on TPU
    logits_fq = apply_fn(params, batch["images"])
    logits_q = apply_q(qparams, batch["images"])
    acc = float(jnp.mean(jnp.argmax(logits_q, -1) == batch["labels"]))
    drift = float(jnp.max(jnp.abs(logits_q - logits_fq)))
    print(f"  packed {quantized_fraction(qparams)*100:.0f}% of param bytes "
          f"to int8 codes; serving acc {acc*100:.0f}%, "
          f"max logit drift vs fake-quant {drift:.2e}")
    # the demo's claim: deployed packed-code numerics == QAT numerics
    assert drift < 1e-3 * float(jnp.max(jnp.abs(logits_fq)) + 1), drift
    return acc


def main():
    print("1. training SqueezeNet (logq6 fake-quant = accelerator "
          "numerics):")
    loss, acc, params, apply_fn, batch = train_quantized_cnn()
    if acc <= 0.5:  # QAT-from-scratch on 32 samples is seed-sensitive
        print(f"  (warning: train acc only {acc*100:.0f}% this run)")

    print("\n2. serving with packed int8 log codes (kernels/ops.conv2d "
          "dispatch):")
    acc_q = serve_packed(params, apply_fn, batch)
    assert abs(acc_q - acc) < 0.2, "packed-weight serving lost the model"

    print("\n3. deploying onto the NeuroMAX grid (dataflow model):")
    for net in NETWORKS:
        perf = run_network(net)
        print(f"  {net:13s} util {perf.mean_layer_utilization*100:5.1f}%  "
              f"{perf.throughput_gops_paper:6.1f} GOPS  "
              f"latency {perf.latency_ms:7.2f} ms  "
              f"DDR {perf.ddr_bytes_log/2**20:6.1f} MiB/inference "
              f"(vs {perf.ddr_bytes_fp16/2**20:.1f} MiB fp16 — "
              f"{perf.ddr_bytes_fp16/perf.ddr_bytes_log:.2f}× saved)")
    print("\nThe log codes cut DDR traffic ≈2.3× — on TPU the same codes cut "
          "HBM weight traffic 2.67× vs bf16.")


if __name__ == "__main__":
    main()
