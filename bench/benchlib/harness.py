"""One run of one cell: find it by name, build the program's serving engine
for it, warm it up, drive the timed window, check what it served against
the plain reference, and return the result line.

A cell (``workloads`` in ``BENCHMARK.json``) names a configuration file,
a traffic mix (``traffic/<name>.json`` under the benchmark's paths) and
per-layer metrics (``metrics/<name>.py``, each with ``read(run)``).  The
configuration file names its model module (``<model>.py`` beside it), which
makes the weights, holds the plain reference and counts the work a step
needs.  Adding a cell, a configuration or a metric adds files; nothing here
names one.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from . import peaks as peaks_mod
from . import xtrace
from .traffic import Traffic

COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
GIB = float(1 << 30)


class BenchError(Exception):
    """The run cannot give a result (no chip, unknown cell, bad config)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: object
    end_to_end: list
    per_layer: list
    readers: dict


def _load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find(root: Path, paths: list, rel: str) -> Path:
    for p in paths:
        f = root / p / rel
        if f.is_file():
            return f
    raise BenchError(f"no {rel} under {paths}")


def load_cell(root: Path, name: str, entry: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, or, given ``entry`` (the
    keys of a ``workloads`` entry), a cell that is not declared there, with
    its configuration at ``configs/<config>.json`` (for calibration)."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if entry is None and name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = entry or cells[name]
    files = {c["name"]: root / c["file"] for c in spec["configs"]}
    conf_file = files.get(w["config"]) or _find(
        root, spec["paths"], f"configs/{w['config']}.json")
    config = json.loads(conf_file.read_text())
    traffic = json.loads(_find(root, spec["paths"],
                               f"traffic/{w['traffic']}.json").read_text())
    model = _load_module(conf_file.parent / f"{config['model']}.py")

    def ours(m):
        return name in m.get("workloads", [name])

    e2e = [m for m in spec["end_to_end"] if ours(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    readers = {m["name"]: _load_module(
        _find(root, spec["paths"], f"metrics/{m['name']}.py"))
        for m in per_layer}
    return Cell(name, int(w["chips"]), config, traffic, model, e2e,
                per_layer, readers)


def seed_key(jax, seed: int):
    """A PRNG key from every bit of a seed of any size."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


class CompileClock:
    """Seconds JAX spends tracing, lowering, compiling and fetching cached
    programs, how many programs it built (JAX reports a backend compile
    for a program fetched from the persistent cache too) and how many of
    them came from the cache (its monitoring events)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration_secs, **_):
            if event in COMPILE_EVENTS:
                self.seconds += duration_secs
            if event == BACKEND_COMPILE:
                self.compiles += 1

        def on_event(event, **_):
            if event == CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.seconds, self.compiles, self.cache_hits


@dataclass
class Run:
    """What a per-layer reader reads."""
    cell: Cell
    peak: dict
    setup_compile_s: float
    trace: Optional[xtrace.Reduced]

    def prefill_work(self, x) -> tuple:
        return self.cell.model.prefill_work(self.cell.config["config"],
                                            x.batch, x.seq)

    def decode_work(self, x) -> tuple:
        return self.cell.model.decode_work(self.cell.config["config"],
                                           x.batch, x.held)

    def needed_s(self, flops: float, byts: float) -> float:
        return max(flops / self.peak["bf16_flop_s"],
                   byts / self.peak["hbm_byte_s"])


def check_program_config(cell: Cell, pcfg) -> None:
    want = cell.model.program_expect(cell.config["config"])
    bad = {k: (getattr(pcfg, k), v) for k, v in want.items()
           if getattr(pcfg, k) != v}
    if bad:
        raise BenchError(f"program config {pcfg.name} differs from "
                         f"{cell.config['name']}: {{field: (program, "
                         f"file)}} = {bad}")


def annotate_engine(eng, jax, calls: list) -> None:
    """Wrap the engine's prefill and decode programs in host spans, and log
    each call's batch and, for decode, the positions its lanes hold."""
    if not (hasattr(eng, "prefill_fn") and hasattr(eng, "decode_fn")):
        return
    prefill_fn, decode_fn = eng.prefill_fn, eng.decode_fn
    state = {"held": 0}

    def prefill(params, batch):
        b, s = batch["tokens"].shape
        calls.append(("prefill", int(b), int(s)))
        state["held"] = int(s)
        with jax.profiler.TraceAnnotation(xtrace.SPAN_PREFILL):
            return prefill_fn(params, batch)

    def decode(params, cache, tok):
        calls.append(("decode", int(tok.shape[0]), state["held"]))
        state["held"] += int(tok.shape[1])
        with jax.profiler.TraceAnnotation(xtrace.SPAN_DECODE):
            return decode_fn(params, cache, tok)

    eng.prefill_fn, eng.decode_fn = prefill, decode


def drain(jax) -> None:
    """Wait until the device has run everything dispatched so far (it runs
    programs in order): ``generate`` returns with its last decode step
    still queued, since nothing reads that step's token."""
    jax.block_until_ready(jax.numpy.zeros(()) + 1)


def compare(cell: Cell, jax, seed: int, traffic: Traffic, calls_out: list,
            quant: str = "none") -> dict:
    """Run the plain reference over a sample, drawn from the seed, of the
    requests the window finished: each prompt with the tokens served for it.
    Returns the widest gap by which a served token's reference logit lies
    below the reference's best, and the count of served tokens outside the
    vocabulary.  With ``quant`` other than "none" it also returns the gap of
    the tokens that reference, computed at that precision, puts first."""
    import jax.numpy as jnp

    c = cell.config["config"]
    V = c["vocab_size"]
    G = traffic.gen_len
    sample = traffic.check_sample(len(calls_out))
    rows = np.stack([np.concatenate([calls_out[i][0][s], calls_out[i][1][s]])
                     for i, s in sample])                  # (n, P + G)
    weights = jax.jit(lambda k: cell.model.make_weights(c, k))(
        seed_key(jax, seed))

    def reference(q):
        return jax.jit(lambda w, t: cell.model.reference_logits(
            c, w, t, G, quant=q))

    ref, ctl = reference("none"), reference(quant)
    gap = ctl_gap = 0.0
    oov = 0
    n = traffic.check_block_rows
    for b in range(0, len(rows), n):
        blk = rows[b:b + n]
        served = blk[:, -G:]
        oov += int((served >= V).sum())
        with jax.default_matmul_precision("highest"):
            logits = ref(weights, jnp.asarray(blk[:, :-1]))
            best = logits.max(-1)
            got = jnp.take_along_axis(
                logits, jnp.asarray(np.minimum(served, V - 1))[..., None],
                -1)[..., 0]
            ok = jnp.asarray(served < V)
            gap = max(gap, float(jnp.where(ok, best - got, 0.0).max()))
            if quant != "none":
                first = ctl(weights, jnp.asarray(blk[:, :-1])).argmax(-1)
                alt = jnp.take_along_axis(logits, first[..., None], -1)[..., 0]
                ctl_gap = max(ctl_gap, float((best - alt).max()))
    out = {"max_logit_gap": gap, "served_out_of_vocab": oov}
    if quant != "none":
        out["control_gap"] = ctl_gap
    del weights
    return out


@dataclass
class Setup:
    cell: Cell
    jax: object
    devices: list
    peak: Optional[dict]
    pcfg: object
    clock: CompileClock


def prepare(root: Path, workload: str, *, require_tpu: bool = True,
            peak: Optional[dict] = None, log=print,
            program_overrides: Optional[dict] = None,
            entry: Optional[dict] = None) -> Setup:
    """Find the cell, point JAX's compile cache into the checkout, check the
    device and the program's configuration.  ``program_overrides`` replace
    fields of the program's configuration (for diagnosis only)."""
    cell = load_cell(root, workload, entry)

    import jax

    from repro.configs import get_config, get_smoke_config

    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock(jax)

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} "
        f"count={len(devices)}")
    if require_tpu:
        if dev.platform != "tpu":
            raise BenchError(f"no TPU: JAX's devices are {dev.platform}")
        if len(devices) < cell.chips:
            raise BenchError(f"{workload} needs {cell.chips} chips, "
                             f"JAX finds {len(devices)}")
        peak = peaks_mod.lookup(dev.device_kind)

    conf = cell.config
    # "smoke": the program's reduced variant, for tests off the chip
    pcfg = (get_smoke_config if conf.get("program_variant") == "smoke"
            else get_config)(conf["program_arch"])
    check_program_config(cell, pcfg)
    if program_overrides:
        import dataclasses

        pcfg = dataclasses.replace(pcfg, **program_overrides)
    return Setup(cell, jax, devices, peak, pcfg, clock)


def make_params(su: Setup, seed: int):
    c = su.cell.config["config"]
    return su.jax.jit(lambda k: su.cell.model.make_weights(c, k))(
        seed_key(su.jax, seed))


def make_engine(su: Setup, params, traffic: Traffic):
    """The engine as the program's ``serve_real_model`` builds it."""
    from repro.serving.engine import JaxServeEngine

    return JaxServeEngine(su.pcfg, params, n_slots=traffic.slots,
                          max_len=traffic.prompt_len + traffic.gen_len + 4,
                          admission_kind="gcr")


def make_traffic(su: Setup, seed: int) -> Traffic:
    conf = su.cell.config
    return Traffic(su.cell.traffic, slots=int(conf["slots"]),
                   vocab=conf["config"]["vocab_size"], seed=seed)


def run(root: Path, workload: str, seed: int, seconds: float, trace: bool,
        *, t0: float, require_tpu: bool = True, peak: Optional[dict] = None,
        log: Callable[[str], None] = lambda s: print(s, file=sys.stderr,
                                                     flush=True)) -> dict:
    """One run; returns the result line as a dict.  ``t0`` is the process's
    start on ``time.perf_counter``.  Off a TPU (tests) pass
    ``require_tpu=False`` and a ``peak``."""
    su = prepare(root, workload, require_tpu=require_tpu, peak=peak, log=log)
    jax, cell, clock, dev = su.jax, su.cell, su.clock, su.devices[0]
    conf = cell.config
    traffic = make_traffic(su, seed)
    params = make_params(su, seed)
    eng = make_engine(su, params, traffic)
    calls: list = []
    if trace:
        annotate_engine(eng, jax, calls)
    eng.generate(traffic.warmup_prompts(), gen_len=1)
    drain(jax)
    calls.clear()
    setup_s = time.perf_counter() - t0
    setup_compile_s, setup_compiles, setup_hits = clock.snapshot()
    log(f"setup: setup_s={setup_s:.3f} compile_s={setup_compile_s:.3f} "
        f"compiles={setup_compiles} cache_hits={setup_hits}")

    tdir = Path(tempfile.mkdtemp(prefix="bench-trace-")) if trace else None
    outs = []
    call_s = []
    if trace:
        # runtime host spans only: the Python tracer slows the host loop
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    w0 = time.perf_counter()
    while True:
        prompts = traffic.call_prompts(len(outs))
        span = (jax.profiler.TraceAnnotation(xtrace.SPAN_WINDOW) if trace
                else nullcontext())
        c0 = time.perf_counter()
        with span:
            toks = eng.generate(prompts, gen_len=traffic.gen_len)
            if trace:
                drain(jax)
        call_s.append(time.perf_counter() - c0)
        outs.append((prompts, toks))
        if trace or time.perf_counter() - w0 >= seconds:
            break
    window_s = time.perf_counter() - w0
    if trace:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    s1, c1, h1 = clock.snapshot()
    log(f"window: calls={len(outs)} window_s={window_s:.3f} "
        f"call_s={[round(c, 3) for c in call_s]} "
        f"compiles_in_window={c1 - setup_compiles} "
        f"cache_fetches_in_window={h1 - setup_hits} "
        f"compile_s_in_window={s1 - setup_compile_s:.3f}")

    n_req = len(outs) * traffic.streams
    tokens = n_req * traffic.gen_len
    del eng, params
    gc.collect()

    reduced = None
    if trace:
        files = sorted(tdir.rglob("*.xplane.pb"))
        if files:
            reduced = xtrace.reduce(xtrace.load(files[-1]), calls)
        shutil.rmtree(tdir, ignore_errors=True)

    check = compare(cell, jax, seed, traffic, outs)
    limit = conf["limits"]["max_logit_gap"]
    compared = {
        "max_logit_gap": {"value": check["max_logit_gap"], "limit": limit},
        "served_out_of_vocab": {"value": check["served_out_of_vocab"],
                                "limit": 0},
    }
    correct = (limit is not None and check["max_logit_gap"] <= limit
               and check["served_out_of_vocab"] == 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(su.devices), "memory_peak_bytes": mem_peak}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if not trace:
        values = {"out_tok_s": tokens / window_s,
                  "peak_hbm_gib": mem_peak / GIB,
                  "setup_s": setup_s}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items() if k in units}
        result = {"correct": correct, "attempted": n_req, "failed": 0,
                  "metrics": metrics, "device": device}
    else:
        r = Run(cell, su.peak, setup_compile_s, reduced)
        metrics = {}
        for name, reader in cell.readers.items():
            v = reader.read(r)
            if v is not None and math.isfinite(v):
                metrics[name] = {"value": v, "unit": units[name]}
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
        result = {"correct": correct, "attempted": n_req, "failed": 0,
                  "metrics": metrics, "device": device}
        if reduced is not None:
            result["breakdown"] = breakdown(reduced)
            log(f"trace: window_s={reduced.window_s:.3f} "
                f"busy_s={reduced.busy_s:.3f} matched={reduced.matched} "
                f"traced_out_tok_s={tokens / window_s:.3f}")
    result["compared"] = compared
    return result


def breakdown(r: xtrace.Reduced) -> dict:
    gaps: dict = {}
    for s, e, name in r.idle_gaps:
        gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e9
    return {"device_ops": [[n, t] for n, t in r.device_ops[:10]],
            "idle_gaps": sorted(([n, t] for n, t in gaps.items()),
                                key=lambda x: -x[1])[:10]}


def main(argv, t0: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    sys.path.insert(0, str(root / "src"))
    try:
        result = run(root, a.workload, a.seed, a.seconds, bool(a.trace),
                     t0=t0)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for k, v in result["compared"].items():
        print(f"compared {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    return 0
