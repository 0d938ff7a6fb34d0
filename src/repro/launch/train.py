"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Selects an assigned architecture (optionally reduced), builds the mesh over
the available devices, constructs sharded train state, and runs the full
production loop: GCR-locked prefetch pipeline -> jitted sharded train step
(remat + optional microbatching) -> async atomic checkpoints -> automatic
resume from the latest checkpoint.

On a real TPU pod this same entry point runs under the usual multi-host
launcher (one process per host; ``jax.distributed.initialize`` is called
when ``TPU_WORKER_HOSTNAMES`` lists more than one host); on one host it
runs alone, and on a CPU it runs the reduced configs.
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from ..checkpoint import CheckpointManager
from ..config import OptimizerConfig
from ..configs import ARCHS, get_config, get_smoke_config
from ..data import PrefetchPipeline, SyntheticTokens
from ..parallel import ShardingRules
from ..steps import init_train_state, make_train_step, train_state_shapes
from .compile_cache import use_compile_cache
from .mesh import make_host_mesh, make_production_mesh


def build_sharded_train(cfg, mesh, opt_cfg: OptimizerConfig, *, seed: int = 0,
                        microbatches: int = 1):
    """Train state placed on ``mesh`` by ``ShardingRules`` (ZeRO-1 moments)
    and the jitted step over it.  The state is initialised straight into its
    shards, so no device holds it whole.

    Returns (step_fn, params, opt); ``step_fn(params, opt, batch)``
    donates params and opt and returns (params, opt, metrics)."""
    rules = ShardingRules(cfg, mesh)
    p_shape, _ = train_state_shapes(cfg)
    p_sh = jax.tree.map(rules.sharding, rules.param_specs(p_shape))
    m_sh = jax.tree.map(rules.sharding, rules.opt_specs(p_shape))
    o_sh = {"m": m_sh, "v": m_sh,
            "count": rules.sharding(jax.sharding.PartitionSpec())}
    params, opt = jax.jit(lambda k: init_train_state(cfg, k),
                          out_shardings=(p_sh, o_sh))(jax.random.key(seed))
    step_fn = jax.jit(
        make_train_step(cfg, opt_cfg, rules, microbatches=microbatches),
        in_shardings=(p_sh, o_sh, None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1))
    return step_fn, params, opt


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 production mesh (TPU pod)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    hosts = [h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
             if h]
    if len(hosts) > 1:                         # multi-host TPU launch
        jax.distributed.initialize()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(model=args.model_parallel))
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} devices={mesh.devices.size}")

    opt_cfg = OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                              total_steps=args.steps)
    step_fn, params, opt = build_sharded_train(
        cfg, mesh, opt_cfg, seed=args.seed, microbatches=args.microbatches)

    ckpt_dir = args.ckpt_dir or f"/tmp/repro_{cfg.name}"
    mgr = CheckpointManager(ckpt_dir, keep=2, async_save=True)
    start = 0
    if mgr.latest_step() is not None:
        start, state, extra = mgr.restore()
        params = jax.tree.map(lambda a, b: jnp.asarray(b).astype(a.dtype),
                              params, state["params"])
        opt = jax.tree.map(lambda a, b: jnp.asarray(b).astype(a.dtype),
                           opt, state["opt"])
        print(f"resumed from step {start}")

    src = SyntheticTokens(cfg, seq_len=args.seq, global_batch=args.batch,
                          seed=args.seed)
    pipe = PrefetchPipeline(src, depth=4, workers=2, start_at=start,
                            use_gcr=True)
    t0 = time.perf_counter()
    tokens_done = 0
    try:
        for i, batch in iter(pipe):
            if i >= args.steps:
                break
            params, opt, metrics = step_fn(params, opt, batch)
            tokens_done += args.batch * args.seq
            if (i + 1) % 10 == 0:
                dt = time.perf_counter() - t0
                print(f"step {i+1:5d} loss {float(metrics['loss']):.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"{tokens_done/dt:,.0f} tok/s")
            if (i + 1) % args.ckpt_every == 0:
                mgr.save(i + 1, {"params": params, "opt": opt},
                         extra={"next_batch": i + 1})
    finally:
        pipe.stop()
    mgr.save(args.steps, {"params": params, "opt": opt},
             extra={"next_batch": args.steps})
    mgr.wait()
    print(f"done; checkpoints in {ckpt_dir}")


if __name__ == "__main__":
    main()
