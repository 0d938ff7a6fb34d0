import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes, and extract the roofline inputs.

MUST be run as a module entry point (``python -m repro.launch.dryrun``):
the two lines above run before any other import so jax sees 512 host
devices.  Never set that flag globally - tests and benches want 1 device.

Per cell it records to ``experiments/dryrun/<mesh>/<arch>__<shape>.json``:
  * memory_analysis (bytes per device: args/outputs/temps/peak)
  * cost_analysis   (HLO flops / bytes accessed)
  * collective_bytes by op kind, parsed from the post-SPMD optimized HLO
  * model flops (6ND analytic) and roofline terms for TPU v5e

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.config import SHAPES, V5E, MeshConfig, OptimizerConfig, cells_for
from repro.configs import ARCHS, get_config
from repro.launch.hlo_analysis import analyze_hlo, normalize_cost_analysis
from repro.launch.mesh import make_production_mesh
from repro.parallel import ShardingRules
from repro.steps import (batch_shapes, decode_state_shapes, make_decode_step,
                         make_prefill, make_train_step, train_state_shapes)

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# Per-cell perf overrides from the hillclimbing log (EXPERIMENTS.md
# section Perf).  Baselines were recorded without them.
PERF_OVERRIDES = {
    # H3 (remat off for qwen3-0.6b train) was tried and REFUTED: peak
    # 3.25 -> 22.6 GiB (OOM on v5e) and memory term +21%.  See
    # EXPERIMENTS.md section Perf.
    # H-M1: mixtral train exceeds HBM at 1 microbatch (34.7 GiB peak);
    # 4-way gradient accumulation divides the activation working set.
    # H-M2: accumulate in bf16 (the f32 full-bank accumulators were the
    # largest buffers).
    ("mixtral-8x7b", "train_4k"): {"microbatches": 4,
                                   "accum_dtype": "bfloat16"},
}

# HLO ops whose operand bytes count as collective traffic.
_COLLECTIVE_RE = re.compile(
    r"(\ball-gather|\ball-reduce|\breduce-scatter|\ball-to-all|"
    r"\bcollective-permute)")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _bytes_of_shape(txt: str) -> int:
    """Total bytes of the (possibly tuple) result shape in an HLO line."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op, by kind.

    Uses the post-SPMD optimized HLO (``compiled.as_text()``); result shape
    ~= moved payload per chip for all-gather/all-reduce (upper bound).
    """
    out: dict = {}
    op_re = re.compile(
        r"=\s*(?:\([^)]*\)|[a-z0-9_]+\[[0-9,]*\](?:\{[0-9,]*\})?)?\s*"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(")
    for line in hlo_text.splitlines():
        s = line.lstrip()
        if "=" not in s:
            continue
        m = op_re.search(s)
        if not m:
            continue
        kind = m.group(1)
        lhs = s.split("=")[0]
        out.setdefault(kind, {"count": 0, "bytes": 0})
        out[kind]["count"] += 1
        out[kind]["bytes"] += _bytes_of_shape(lhs)
    return out


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   chips: int) -> dict:
    return {
        "compute_s": flops / (chips * V5E.peak_flops),
        "memory_s": hbm_bytes / (chips * V5E.hbm_bw),
        # 2 links usable per axis hop on a 2D torus slice (conservative)
        "collective_s": coll_bytes / (chips * V5E.ici_bw * 2),
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rules = ShardingRules(cfg, mesh, shape)
    t0 = time.time()

    with mesh:
        batch = batch_shapes(cfg, shape)
        batch_sh = jax.tree.map(rules.sharding, rules.batch_specs(batch))

        if shape.kind == "train":
            params, opt = train_state_shapes(cfg)
            p_sh = rules.param_shardings(params)
            moment_sh = jax.tree.map(
                rules.sharding, rules.opt_specs(params, zero1=True))
            o_sh = {"m": moment_sh, "v": moment_sh,
                    "count": rules.sharding(jax.sharding.PartitionSpec())}
            over = PERF_OVERRIDES.get((arch, shape_name), {})
            step_fn = make_train_step(
                cfg, OptimizerConfig(), rules,
                remat=over.get("remat", True),
                microbatches=over.get("microbatches", 1),
                accum_dtype=jnp.dtype(over.get("accum_dtype", "float32")))
            jitted = jax.jit(
                step_fn,
                in_shardings=(p_sh, o_sh, batch_sh),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(params, opt, batch)
        elif shape.kind == "prefill":
            params, _ = train_state_shapes(cfg)
            p_sh = rules.param_shardings(params)
            step_fn = make_prefill(cfg, max_len=shape.seq_len, rules=rules)
            jitted = jax.jit(step_fn, in_shardings=(p_sh, batch_sh))
            lowered = jitted.lower(params, batch)
        else:  # decode
            params, _ = train_state_shapes(cfg)
            p_sh = rules.param_shardings(params)
            caches = decode_state_shapes(cfg, shape)
            c_sh = rules.cache_shardings(caches, shape.global_batch)
            step_fn = make_decode_step(cfg, rules)
            jitted = jax.jit(step_fn,
                             in_shardings=(p_sh, c_sh, batch_sh["tokens"]),
                             donate_argnums=(1,))
            lowered = jitted.lower(params, caches, batch["tokens"])

        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = normalize_cost_analysis(compiled.cost_analysis())
    hlo = compiled.as_text()
    walk = analyze_hlo(hlo)       # loop-aware per-device flops/bytes/colls
    del hlo

    # Per-device quantities (the SPMD program IS the per-device program).
    flops = float(walk["flops"])
    hbm = float(walk["bytes"])
    coll_total = float(walk["collective_bytes"])

    # MODEL_FLOPS: 6 N D for train, 2 N D for inference forward (global)
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch  # one token per sequence
        model_flops = 2.0 * n_active * tokens

    terms = {
        "compute_s": flops / V5E.peak_flops,
        "memory_s": hbm / V5E.hbm_bw,
        # 2 usable links per sharded axis hop on the v5e 2D torus
        "collective_s": coll_total / (V5E.ici_bw * 2),
    }
    dominant = max(terms, key=terms.get)

    rec = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes",
                                  mem.temp_size_in_bytes
                                  + mem.argument_size_in_bytes),
        },
        # per-device, loop-corrected (see hlo_analysis.py)
        "hlo_flops": flops,
        "hlo_bytes": hbm,
        "collectives": walk["collectives"],
        "collective_bytes": coll_total,
        # raw cost_analysis for reference (known to undercount loop bodies)
        "xla_cost_analysis": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "model_flops": model_flops,
        "model_flops_per_chip": model_flops / chips,
        "useful_flops_ratio": (model_flops / chips) / flops if flops else None,
        "roofline": terms,
        "dominant": dominant,
        "params": cfg.param_count(),
        "active_params": n_active,
    }

    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape_name}.json"
    path.write_text(json.dumps(rec, indent=1))
    return rec


def all_cells():
    for arch in ARCHS:
        cfg = get_config(arch)
        for shape in cells_for(cfg):
            yield arch, shape.name


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    mesh_tag = "2x16x16" if args.multi_pod else "16x16"
    out_dir = OUT_DIR / mesh_tag

    cells = (list(all_cells()) if args.all
             else [(args.arch, args.shape)])
    failures = []
    for arch, shape in cells:
        path = out_dir / f"{arch}__{shape}.json"
        if args.skip_existing and path.exists():
            print(f"skip {arch}/{shape} (exists)")
            continue
        try:
            rec = run_cell(arch, shape, args.multi_pod, out_dir)
            t = rec["roofline"]
            print(f"OK  {arch:22s} {shape:12s} mesh={mesh_tag} "
                  f"compile={rec['compile_s']:7.1f}s "
                  f"peak/dev={rec['memory']['temp_bytes']/2**30:6.2f}GiB "
                  f"comp={t['compute_s']:.3e}s mem={t['memory_s']:.3e}s "
                  f"coll={t['collective_s']:.3e}s dom={rec['dominant']}",
                  flush=True)
        except Exception as e:  # noqa: BLE001 - report and continue
            failures.append((arch, shape, repr(e)))
            print(f"FAIL {arch}/{shape}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall cells compiled")


if __name__ == "__main__":
    main()
