"""Fault-tolerant training driver.

Two execution modes:
  pjit (default)   mesh-sharded train step (same path the dry-run lowers)
  ddp-compress     shard_map data-parallel with int8 all-reduce gradient
                   compression + error feedback (distributed/compression.py)

Fault tolerance: atomic async checkpoints every --ckpt-every steps, exact
resume (--resume) including data-pipeline position (pure function of step),
so a preempted job continues bit-identically. Elastic: checkpoints are
topology-free; restore re-shards onto whatever mesh the restart has.

Example (CPU container, reduced config):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b --smoke \
      --steps 200 --ckpt-dir /tmp/ckpt --ckpt-every 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_arch
from repro.data.tokens import EmbeddingPipeline, TokenPipeline
from repro.distributed.compression import compressed_psum_tree
from repro.launch.mesh import shardings_for
from repro.launch.steps import make_train_step
from repro.models import init_params, param_specs, train_loss
from repro.models import model as MODEL
from repro.models.sharding import activation_sharding
from repro.optim import adamw, clip_by_global_norm, warmup_cosine


def build_mesh(spec: str | None):
    devs = jax.devices()
    if spec is None:
        return Mesh(np.array(devs), ("data",))
    parts = [int(p) for p in spec.split("x")]
    names = ("data", "model")[:len(parts)]
    return Mesh(np.array(devs[:int(np.prod(parts))]).reshape(parts), names)


def make_pipeline(cfg, batch, seq, seed):
    if MODEL.has_token_embed(cfg):
        return TokenPipeline(vocab_size=cfg.vocab_size, batch=batch,
                             seq_len=seq, seed=seed)
    return EmbeddingPipeline(d_model=cfg.d_model, vocab_size=cfg.vocab_size,
                             batch=batch, seq_len=seq, seed=seed)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm_360m")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None, help="e.g. 4x2 = data x model")
    ap.add_argument("--mode", default="pjit", choices=["pjit", "ddp-compress"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = get_arch(args.arch, smoke=args.smoke)
    mesh = build_mesh(args.mesh)
    pipe = make_pipeline(cfg, args.batch, args.seq, args.seed)
    opt = adamw(warmup_cosine(args.lr, args.warmup, args.steps))

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = opt.init(params)
    start_step = 0

    cm = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if cm and args.resume and cm.latest_step() is not None:
        (params, opt_state), start_step = cm.restore((params, opt_state))
        print(f"[train] resumed from step {start_step}")

    if args.mode == "pjit":
        psh = shardings_for(param_specs(cfg), mesh)
        params = jax.device_put(params, psh)
        opt_state = jax.device_put(
            opt_state,
            shardings_for(opt.state_specs(param_specs(cfg), params), mesh))
        bsh = NamedSharding(mesh, P("data"))
        fn = make_train_step(cfg, opt)
        ctx = activation_sharding(mesh)
        with mesh, ctx:
            step_fn = jax.jit(fn, donate_argnums=(0, 1))
    else:
        # shard_map DDP with int8 compressed all-reduce + error feedback
        def ddp_step(params, opt_state, resid, step, batch):
            def loss_fn(p, b):
                return train_loss(p, cfg, b)[0]

            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads = jax.tree.map(lambda g, r: g.astype(jnp.float32) + r,
                                 grads, resid)
            grads, new_resid = compressed_psum_tree(grads, "data")
            loss = jax.lax.pmean(loss, "data")
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            new_params, new_state = opt.update(grads, opt_state, params, step)
            return new_params, new_state, new_resid, loss, gnorm

        resid = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        mapped = shard_map(
            ddp_step, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P("data")),
            out_specs=(P(), P(), P(), P(), P()),
            check_vma=False)
        step_fn = jax.jit(mapped, donate_argnums=(0, 1, 2))

    t0 = time.time()
    step = start_step
    try:
        while step < args.steps:
            batch = pipe.global_batch(step)
            if args.mode == "pjit":
                params, opt_state, _, metrics = step_fn(
                    params, opt_state, jnp.int32(step), batch)
                loss = float(metrics["loss"])
            else:
                params, opt_state, resid, loss, _ = step_fn(
                    params, opt_state, resid, jnp.int32(step), batch)
                loss = float(loss)
            step += 1
            if step % args.log_every == 0 or step == args.steps:
                dt = (time.time() - t0) / max(step - start_step, 1)
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"{dt*1e3:.0f} ms/step", flush=True)
            if cm and (step % args.ckpt_every == 0 or step == args.steps):
                cm.save(step, (params, opt_state), wait=False)
    finally:
        if cm:
            cm.wait_for_save()
    print(f"[train] done at step {step}; final loss {loss:.4f}")


if __name__ == "__main__":
    main()
