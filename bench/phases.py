"""Device idle by the serving engine's phases, for traced calls of a cell on
the chip.  Not run by the benchmark's runs.

    python3 bench/phases.py --workload <cell> --seeds 1,2,3 [--save PATH]

For each seed, in one process: the cell's weights, one untraced call of the
cell's traffic and one traced call (both waited for to the end), then the
traced call's trace reduced as a run reduces it and its engine spans moved
onto the device's clock (``benchlib/spans.py``).  Prints one JSON line per
seed: both calls' seconds, the device idle inside the ``serve.readout``
spans (mean per span) and the ``serve.dispatch`` spans, the engine's
counters over the traced call, ``decode_gap_ms`` and ``idle_share`` as the
benchmark's readers read them, and the clock bracket; and, on standard
error, the idle per phase (``Phases.line``).  ``--save`` then traces one
more call, a wave of 4 tokens, and writes its modules and host spans with
``xtrace.save_json`` (a trace for the tests).
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

from benchlib import harness, spans, xtrace  # noqa: E402
from repro.serving.engine import SPAN_DISPATCH, SPAN_READOUT  # noqa: E402

SAVED_GEN_LEN = 4


def traced(jax, eng, prompts, gen_len, calls):
    """One call inside ``bench.window`` under the profiler: (seconds, the
    trace's events, the engine's counters over the call)."""
    tdir = Path(tempfile.mkdtemp(prefix="bench-phases-"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    before = dataclasses.asdict(eng.stats)
    calls.clear()
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(xtrace.SPAN_WINDOW):
            eng.generate(prompts, gen_len=gen_len)
            harness.drain(jax)
        seconds = time.perf_counter() - t
    finally:
        jax.profiler.stop_trace()
    counts = {k: v - before[k] for k, v in dataclasses.asdict(eng.stats)
              .items()}
    events = xtrace.load(sorted(tdir.rglob("*.xplane.pb"))[-1])
    shutil.rmtree(tdir, ignore_errors=True)
    return seconds, events, counts


def measure(su, eng, traffic, calls, log) -> dict:
    """The untraced and the traced call of one seed, and what the traced
    one shows."""
    jax = su.jax
    t = time.perf_counter()
    eng.generate(traffic.call_prompts(0), gen_len=traffic.gen_len)
    harness.drain(jax)
    untraced_s = time.perf_counter() - t
    traced_s, events, counts = traced(jax, eng, traffic.call_prompts(1),
                                      traffic.gen_len, calls)
    red = xtrace.reduce(events, calls)
    ph = spans.phases(events, red)
    run = harness.Run(su.cell, su.peak, 0.0, red)
    line = {"untraced_call_s": untraced_s, "traced_call_s": traced_s,
            "engine": counts,
            "host_reads_per_step": counts["host_reads"]
            / counts["decode_steps"]}
    for name in ("decode_gap_ms", "idle_share"):
        line[name] = su.cell.readers[name].read(run)
    if red is not None:
        decodes = len(red.of_kind("decode"))
        line.update(matched=red.matched, executions=len(red.executions),
                    executions_per_decode=len(red.executions) / decodes
                    if decodes else None)
    if ph is not None:
        log(ph.line())
        readout, dispatch = ph.idle_in(SPAN_READOUT), ph.idle_in(SPAN_DISPATCH)
        line.update(
            readout_idle_ms=1e3 * sum(readout) / len(readout),
            dispatch_idle_ms=1e3 * sum(dispatch) / len(dispatch),
            idle_s=ph.idle_s(), window_idle_s=red.window_s - red.busy_s,
            clock_lower_ms=None if ph.lower_ns is None else ph.lower_ns / 1e6,
            clock_upper_ms=None if ph.upper_ns is None else ph.upper_ns / 1e6,
            late_starts=ph.late_starts())
    return line


def main(argv=None, root: Path = HERE.parent, require_tpu: bool = True,
         peak=None, out=print) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--save", help="write a short traced call's events here")
    a = ap.parse_args(argv)

    def log(s):
        print(s, file=sys.stderr, flush=True)

    su = harness.prepare(root, a.workload, require_tpu=require_tpu,
                         peak=peak, log=log)
    eng = None
    calls: list = []
    for seed in (int(s) for s in a.seeds.split(",")):
        traffic = harness.make_traffic(su, seed)
        params = harness.make_params(su, seed)
        if eng is None:
            eng = harness.make_engine(su, params, traffic)
            harness.annotate_engine(eng, su.jax, calls)
            eng.generate(traffic.warmup_prompts(), gen_len=1)
        eng.params = params
        line = {"workload": a.workload, "seed": seed}
        line.update(measure(su, eng, traffic, calls, log))
        out(json.dumps(line))
        del params
    if a.save:
        _, events, _ = traced(su.jax, eng,
                              traffic.call_prompts(2)[:traffic.slots],
                              SAVED_GEN_LEN, calls)
        xtrace.save_json([e for e in events if e.line != xtrace.OPS_LINE],
                         calls, a.save)
        log(f"saved {a.save}: {len(events)} events, {len(calls)} calls")
    log(f"total_s={time.perf_counter() - T0:.1f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
