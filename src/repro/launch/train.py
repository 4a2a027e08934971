"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt --ckpt-every 50

Wires together: config registry -> model init -> lifting-derived shardings ->
pjit'd train step -> synthetic data pipeline -> async checkpointing with
restart-resume -> straggler watchdog.  On a real cluster the same driver runs
under ``jax.distributed.initialize`` with the production mesh; here it uses
whatever local devices exist.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.core.hardware import detect_hardware
from repro.data import PipelineConfig, SyntheticLM
from repro.distributed import sharding as shard_rules
from repro.distributed.compression import CompressionConfig
from repro.distributed.fault import Coordinator, ElasticManager, StepWatchdog
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.optim.adamw import AdamWConfig
from repro.train import train_step as ts_mod


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--dp", type=int, default=0, help="0 = all local devices")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    dp = args.dp or max(len(jax.devices()) // args.tp, 1)
    mesh = make_host_mesh(dp=dp, tp=args.tp)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"arch={cfg.name} reduced={args.reduced}")

    comp = CompressionConfig(enabled=args.compress_grads)
    opt_cfg = AdamWConfig(lr_peak=args.lr, warmup_steps=args.warmup,
                          decay_steps=max(args.steps, 2 * args.warmup))
    key = jax.random.PRNGKey(args.seed)

    with mesh:
        state, p_axes = ts_mod.init_state(cfg, key, comp)
        state_axes = ts_mod.state_logical_axes(state, p_axes)
        state_shardings = shard_rules.param_shardings(state, state_axes, mesh)
        state = jax.tree.map(jax.device_put, state, state_shardings)

        data = SyntheticLM(PipelineConfig(cfg.vocab_size, args.seq,
                                          args.batch, seed=args.seed), cfg)
        step_fn = jax.jit(
            ts_mod.make_train_step(cfg, opt_cfg, comp, args.microbatches),
            donate_argnums=(0,),
            compiler_options=detect_hardware().xla_options())

        ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
        start = 0
        if ckpt and ckpt.all_steps():
            state, manifest = ckpt.restore(state, shardings=state_shardings)
            start = manifest["metadata"].get("data_step", manifest["step"])
            print(f"resumed from step {start}")

        coord = Coordinator()
        watchdog = StepWatchdog(coord)
        losses = []
        for step in range(start, args.steps):
            batch = jax.tree.map(jnp.asarray, data.global_batch(step))
            watchdog.start()
            state, metrics = step_fn(state, batch)
            loss = float(metrics["loss"])
            watchdog.stop(step)
            losses.append(loss)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:8.4f} "
                      f"gnorm {float(metrics['grad_norm']):7.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"{watchdog.ema_s or 0:6.3f}s/step", flush=True)
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save_async(step + 1, state,
                                metadata=SyntheticLM.state_dict(step + 1))
        if ckpt:
            ckpt.wait()
        if coord.events:
            print(f"watchdog events: {len(coord.events)}")
        print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
        return losses


if __name__ == "__main__":
    main()
