"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b \
        --reduced --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On the container this runs reduced configs on the host mesh; on a real
cluster the same driver runs full configs on the production mesh
(--production).  Restart the command after a crash and it resumes from the
latest checkpoint (runtime/checkpoint.py), on whatever device count the
restarted world has (resharding restore).
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from ..configs.registry import ARCH_NAMES, get_config
from ..data.pipeline import DataConfig, ShardedLoader
from ..models import sharding, transformer
from ..obs import metrics as obs_metrics
from ..runtime.checkpoint import CheckpointManager
from ..runtime.compile_cache import setup_compile_cache
from ..runtime.monitor import HeartbeatMonitor
from ..training.optimizer import OptimizerConfig
from ..training.train_loop import TrainConfig, init_train_state, train
from .mesh import make_host_mesh, make_production_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_NAMES), default="gemma-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config of the same family")
    ap.add_argument("--production", action="store_true",
                    help="use make_production_mesh() (real cluster)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress", action="store_true",
                    help="log-quant EF gradient compression (beyond-paper)")
    ap.add_argument("--quant", choices=["none", "logq6"], default="none")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.quant != "none":
        import dataclasses
        cfg = dataclasses.replace(cfg, quant=args.quant)

    mesh = make_production_mesh() if args.production else make_host_mesh()
    sharding.set_mesh(mesh)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)}")

    loader = ShardedLoader(DataConfig(
        seq_len=args.seq, global_batch=args.batch, vocab=cfg.vocab,
        seed=args.seed, n_hosts=jax.process_count(),
        host_id=jax.process_index()))

    tcfg = TrainConfig(
        opt=OptimizerConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                            total_steps=args.steps),
        microbatches=args.microbatches, grad_compress=args.grad_compress,
        log_every=args.log_every,
        xent_chunk=min(512, args.seq))
    loss_fn = lambda p, b: transformer.lm_loss(p, b, cfg,
                                               xent_chunk=tcfg.xent_chunk)

    hooks = []
    start_step, state = 0, None
    monitor = HeartbeatMonitor([f"host{i}" for i in
                                range(jax.process_count())])

    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        latest = mgr.latest_step()
        params0 = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
        if latest is not None:
            tpl = jax.eval_shape(
                lambda: init_train_state(params0, tcfg))
            state, start_step = mgr.restore(tpl)
            print(f"resumed from step {start_step}")
        hooks.append(mgr.hook(args.ckpt_every))
        params = params0
    else:
        params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))

    def progress(step, st, metrics):
        print(f"step {step:5d}  loss {metrics['loss']:.4f}  "
              f"gnorm {metrics['grad_norm']:.3f}  "
              f"wall {metrics['wall_s']:.1f}s")
    hooks.append(progress)

    # heartbeats + the step-time histogram are fed from train()'s single
    # per-step event stream (not a separate hook clock)
    registry = obs_metrics.MetricsRegistry()
    state, history = train(loss_fn, params, loader, tcfg,
                           num_steps=args.steps - start_step,
                           start_step=start_step, state=state, hooks=hooks,
                           metrics=registry, monitor=monitor,
                           host=f"host{jax.process_index()}")
    if args.ckpt_dir:
        mgr.save(int(state["step"]), state, sync=True)
    print(f"final loss {history[-1]['loss']:.4f} "
          f"(from {history[0]['loss']:.4f})")
    hist = registry.snapshot()["histograms"].get("train_step_s")
    if hist and hist["count"]:
        rep = monitor.report(int(state["step"]))
        print(f"step time p50 {hist['p50']*1e3:.1f}ms p99 "
              f"{hist['p99']*1e3:.1f}ms over {hist['count']} steps; "
              f"stragglers={list(rep.stragglers)} missing={rep.missing}")
    return history


if __name__ == "__main__":
    main()
