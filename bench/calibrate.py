"""Readings that the check's limits are set from, on the chip, at a cell's
own size.  Not run by the benchmark's runs.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--drops]
        [--override moe_capacity_factor=8]
    python3 bench/calibrate.py --config <name> --traffic <mix> --seeds ...

For each seed, in one process: the cell's weights and one call of the
cell's traffic through the engine (the timed path), then the plain
reference over the same sample of served requests a run compares, and the
fp8 control (the reference computed in float8_e4m3, read at the same
prompts and served tokens).  Prints one JSON line per seed: the program's
widest logit gap (a lower reading), the control's (an upper reading), and
with ``--drops`` the share of routed (token, expert) slots the program's
MoE capacity turns away in a pass over the first wave's prompts.
``--override`` replaces a field of the program's configuration, to find the
cause of a reading; such runs are witnesses, never limits.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from benchlib import harness  # noqa: E402


def _value(v: str):
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            pass
    return v


def main(argv=None, root: Path = HERE.parent, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config", help="a configuration no cell declares yet")
    ap.add_argument("--traffic", default="chat")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--drops", action="store_true")
    ap.add_argument("--override", action="append", default=[])
    a = ap.parse_args(argv)
    overrides = dict((k, _value(v)) for k, v in
                     (o.split("=", 1) for o in a.override))
    entry = None
    if a.config:
        a.workload = f"{a.config}.{a.traffic}"
        entry = {"name": a.workload, "config": a.config,
                 "traffic": a.traffic, "chips": 1}
    su = harness.prepare(root, a.workload, program_overrides=overrides,
                         entry=entry,
                         require_tpu=require_tpu,
                         log=lambda s: print(s, file=sys.stderr, flush=True))
    jax = su.jax
    eng = drops_fn = None
    for seed in (int(s) for s in a.seeds.split(",")):
        t = time.perf_counter()
        traffic = harness.make_traffic(su, seed)
        params = harness.make_params(su, seed)
        if eng is None:
            eng = harness.make_engine(su, params, traffic)
        eng.params = params
        prompts = traffic.call_prompts(0)
        toks = eng.generate(prompts, gen_len=traffic.gen_len)
        line = {"workload": a.workload, "seed": seed, "overrides": overrides}
        if a.drops:
            from repro.models import forward_hidden

            if drops_fn is None:
                drops_fn = jax.jit(lambda p, b: forward_hidden(
                    su.pcfg, p, b, remat=False)[2])
            aux = drops_fn(params, {"tokens": prompts[:traffic.slots]})
            line["moe_drop_frac"] = float(aux.get("moe_drop_frac", 0.0))
        del params
        r = harness.compare(su.cell, jax, seed, traffic, [(prompts, toks)],
                            quant="fp8")
        line.update(program_gap=r["max_logit_gap"],
                    control_gap=r["control_gap"],
                    served_out_of_vocab=r["served_out_of_vocab"],
                    seconds=time.perf_counter() - t)
        print(json.dumps(line), flush=True)
    print(f"total_s={time.perf_counter() - T0:.1f}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
