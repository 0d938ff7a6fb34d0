"""End-to-end driver: train a ~100M-param qwen3-family model with the full
production stack on whatever devices exist (CPU here, TPU pod unchanged):

  sharded train step (ShardingRules) -> AdamW+cosine -> GCR-locked prefetch
  pipeline -> async atomic checkpoints -> kill/restore demo (fault
  tolerance) -> straggler monitor fed with per-step timings.

Run:  PYTHONPATH=src python examples/train_100m.py --steps 60
      (full 100M config; use --small for a seconds-long demo)
"""

import argparse
import dataclasses
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.config import OptimizerConfig
from repro.configs import get_config
from repro.data import PrefetchPipeline, SyntheticTokens
from repro.launch.mesh import make_host_mesh
from repro.parallel import ShardingRules
from repro.runtime import StragglerMitigator
from repro.steps import init_train_state, make_train_step


def build(arch_cfg, steps: int):
    mesh = make_host_mesh(model=1)
    rules = ShardingRules(arch_cfg, mesh)
    params, opt = init_train_state(arch_cfg, jax.random.key(0))
    p_sh = jax.tree.map(rules.sharding, rules.param_specs(params))
    m_sh = jax.tree.map(rules.sharding, rules.opt_specs(params))
    o_sh = {"m": m_sh, "v": m_sh,
            "count": rules.sharding(jax.sharding.PartitionSpec())}
    params = jax.device_put(params, p_sh)
    opt = jax.device_put(opt, o_sh)
    opt_cfg = OptimizerConfig(lr=3e-4, warmup_steps=20, total_steps=steps)
    step = jax.jit(make_train_step(arch_cfg, opt_cfg, rules),
                   in_shardings=(p_sh, o_sh, None),
                   out_shardings=(p_sh, o_sh, None),
                   donate_argnums=(0, 1))
    return params, opt, step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--small", action="store_true",
                    help="tiny config (CI-speed demo)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train100m")
    args = ap.parse_args()

    base = get_config("qwen3-0.6b")
    if args.small:
        cfg = dataclasses.replace(base, n_layers=2, d_model=128, n_heads=4,
                                  n_kv_heads=2, d_ff=256, d_head=32,
                                  vocab_size=2048)
        B, S = 8, 64
    else:
        # ~100M params: 12 layers of the qwen3-0.6b shape, 32k vocab
        cfg = dataclasses.replace(base, n_layers=12, vocab_size=32768)
        B, S = 8, 512

    n_params = cfg.param_count()
    print(f"model: {cfg.name}-derived, {n_params/1e6:.1f}M params; "
          f"batch {B}x{S} on {len(jax.devices())} device(s)")

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    mgr = CheckpointManager(args.ckpt_dir, keep=2, async_save=True)
    src = SyntheticTokens(cfg, seq_len=S, global_batch=B, seed=0)
    pipe = PrefetchPipeline(src, depth=4, workers=2, use_gcr=True)
    params, opt, step = build(cfg, args.steps)
    straggler = StragglerMitigator(list(range(4)), spares=[99])

    half = args.steps // 2
    it = iter(pipe)
    losses = []
    for i, batch in it:
        if i >= half:
            break
        t0 = time.perf_counter()
        params, opt, metrics = step(params, opt, batch)
        dt = time.perf_counter() - t0
        losses.append(float(metrics["loss"]))
        straggler.observe({w: dt * (1 + 0.05 * w) for w in range(4)})
        if (i + 1) % 10 == 0:
            print(f"step {i+1:4d} loss {losses[-1]:.4f} "
                  f"({dt*1e3:.0f} ms/step)")
    pipe.stop()
    mgr.save(half, {"params": params, "opt": opt},
             extra={"next_batch": half})
    mgr.wait()
    print(f"-- simulated failure at step {half}: restoring from "
          f"checkpoint and resuming --")

    # fresh process would do exactly this:
    step_r, state, extra = mgr.restore()
    params2, opt2, step = build(cfg, args.steps)  # rebuild exec + shardings
    params2 = jax.tree.map(lambda a, b: jnp.asarray(b).astype(a.dtype),
                           params2, state["params"])
    opt2 = jax.tree.map(lambda a, b: jnp.asarray(b).astype(a.dtype),
                        opt2, state["opt"])
    pipe2 = PrefetchPipeline.restore(src, extra["next_batch"], workers=2)
    for i, batch in iter(pipe2):
        if i >= args.steps:
            break
        params2, opt2, metrics = step(params2, opt2, batch)
        losses.append(float(metrics["loss"]))
        if (i + 1) % 10 == 0:
            print(f"step {i+1:4d} loss {losses[-1]:.4f} (resumed)")
    pipe2.stop()

    assert losses[-1] < losses[0], "loss did not improve"
    print(f"done: loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"straggler demotions: {straggler.demoted}")


if __name__ == "__main__":
    main()
