"""Smoke run on the TPU through the repo's own entry points.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded training on a 2x2 host only

One chip: serves qwen3-0.6b, then granite-moe-1b-a400m (dropless MoE),
then granite-4.0-h-small (Mamba2 and attention layers, each with an MoE
holding 9 of 72 experts and a shared expert), at their published widths
(random weights from a seed) through ``serve_real_model``, the function
``repro.launch.serve`` runs: twice as many streams as slots (16 on 8, 64 on
32, 32 on 16), so the streams past the slots wait for admission and are
served in a second wave, with 1024-token prompts and 32 generated tokens.
It checks that every token is in the vocabulary, that a second
``generate`` gives the same tokens, that the last-position logits of
``prefill`` match the cache-free pass of ``forward_train`` and that the
engine's prefill program serves their argmax, for an MoE that the engine's
counters show every routed row computed here or counted as routed to an
expert held elsewhere, and that the decode program updates its cache in
place (every step took its input cache; ``memory_analysis()``: the whole
cache, K/V rings and SSM and conv states alike, aliased to the output, no
stacked cache buffer among the temporaries).  Then it runs each case of
``repro.kernels.cases`` natively against its ``ref.py`` oracle: the
Pallas kernels of ``repro.kernels``, among them the MoE combine at both MoE
cells' prefill widths, and the MoE's megablox ``gmm`` at
granite-moe-1b-a400m's and mixtral-8x7b's widths.

``--four-chips``: three steps of qwen3-0.6b training at full width, built as
``repro.launch.train`` builds them, on a (data=2, model=2) mesh and then on
one chip, with the per-step losses compared and the parameter bytes per
device read from ``memory_analysis()``.

Every phase prints its wall time and, apart, the seconds spent compiling and
the persistent-cache hits.  Any failed check or phase raises, so the exit
code is non-zero; on success the last line is one JSON object naming the
device.  Without a TPU it exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# bf16 tolerances, fixed before the first chip run
LOGIT_TOL = 2e-2     # max |prefill - forward| over max |forward| logit
KERNEL_TOL = 2e-2    # max |kernel - oracle| over max |oracle| output
LOSS_RTOL = 1e-2     # |sharded - one-chip| loss over one-chip loss, per step


class Clock:
    """Wall time per phase, with compile seconds and cache hits kept apart
    (from JAX's monitoring events)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, duration_secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration_secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextmanager
    def phase(self, name):
        c0, h0 = self.compile_s, self.cache_hits
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        print(f"phase {name}: wall_s={wall:.3f} "
              f"compile_s={self.compile_s - c0:.3f} "
              f"cache_hits={self.cache_hits - h0}", flush=True)


def _rel_err(got, want):
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check_serving(clock, arch, *, smoke, streams, slots, prompt_len,
                  gen_len):
    import jax
    import numpy as np

    from repro.config import MOE_KINDS
    from repro.launch.serve import serve_real_model
    from repro.models import forward_hidden, lm_logits, prefill

    with clock.phase("serve_first_generate"):
        eng, prompts, tokens = serve_real_model(
            arch, smoke=smoke, streams=streams, slots=slots,
            prompt_len=prompt_len, gen_len=gen_len)
    cfg = eng.cfg
    print(f"serve: arch={cfg.name} d_model={cfg.d_model} "
          f"layers={cfg.n_layers} vocab={cfg.vocab_size} streams={streams} "
          f"slots={slots} prompt_len={prompt_len} gen_len={gen_len} "
          f"parked={eng.stats.streams_parked}")
    if tokens.shape != (streams, gen_len):
        raise AssertionError(f"tokens shape {tokens.shape}")
    if not ((tokens >= 0) & (tokens < cfg.vocab_size)).all():
        raise AssertionError("a generated token is outside the vocabulary")
    moe_layers = sum(k in MOE_KINDS for k in cfg.layer_kinds())
    if moe_layers:
        # every (token, expert) row of every layer, prefill and decode:
        # computed, or routed to an expert held elsewhere
        rows = (streams * (prompt_len + gen_len) * cfg.n_experts_active
                * moe_layers)
        ms = eng.moe_stats
        print(f"serve: moe_rows={ms.rows} (no drop: {rows}) "
              f"moe_rows_elsewhere={ms.rows_elsewhere} "
              f"moe_max_group={ms.max_group}")
        if ms.rows + ms.rows_elsewhere != rows or (
                ms.rows_elsewhere and cfg.n_experts_held == cfg.n_experts):
            raise AssertionError(f"MoE computed {ms.rows} rows and counted "
                                 f"{ms.rows_elsewhere} elsewhere of {rows}")

    with clock.phase("serve_second_generate"):
        again = eng.generate(prompts, gen_len=gen_len)
    if not np.array_equal(tokens, again):
        raise AssertionError(
            f"second generate differs in {(tokens != again).sum()} tokens")
    check_decode_in_place(clock, eng, prompts[:slots])

    with clock.phase("prefill_vs_forward"):
        batch = {"tokens": jax.numpy.asarray(prompts[:slots])}
        logits, _ = jax.jit(lambda p, b: prefill(cfg, p, b,
                                                 max_len=eng.max_len))(
            eng.params, batch)
        ref = jax.jit(lambda p, b: lm_logits(cfg, p, forward_hidden(
            cfg, p, b, remat=False)[0][:, -1:]))(eng.params, batch)
        err = _rel_err(logits, ref)
        tok = np.asarray(eng.prefill_fn(eng.params, batch)[0])[:, 0]
        lg = np.asarray(logits, np.float32)[:, -1]
    print(f"serve: prefill vs forward max_rel_err={err:.3e} "
          f"(tol {LOGIT_TOL:g}) first tokens={tokens[0, :8].tolist()}")
    if not err <= LOGIT_TOL:
        raise AssertionError(f"prefill logits off by {err:.3e}")
    # The engine's prefill program serves the argmax of those logits.  In a
    # MoE a lane may serve another token whose logit equals the best: a
    # tie, which the engine's program may break the other way (granite on
    # a v5e: 2 lanes of 32); such a lane is printed.  A dense model's
    # tokens must all be the argmax.
    best = lg.max(-1)
    got = lg[np.arange(len(tok)), tok]
    off = np.flatnonzero(tok != lg.argmax(-1))
    for j in off:
        print(f"serve: lane {j} serves {tok[j]} at logit {float(got[j])!r}, "
              f"the argmax {lg[j].argmax()} at {float(best[j])!r}")
    if not all(moe_layers and got[j] == best[j] for j in off):
        raise AssertionError(f"prefill_fn's tokens {tok} are not the "
                             f"logits' argmax {lg.argmax(-1)}")


def check_decode_in_place(clock, eng, prompts):
    """The decode program takes its cache (donated) and writes it in place:
    every step the engine ran took its input cache, the program aliases the
    whole cache to its output, and its temporaries hold less than its
    largest stacked buffer (K or V, or a hybrid's SSM states).  The CPU
    backend computes bf16 in float32 and keeps converted copies of the
    stack among its temporaries, so that bound is a TPU's only."""
    import jax

    s = eng.stats
    if s.cache_donated != s.decode_steps:
        raise AssertionError(f"{s.cache_donated} of {s.decode_steps} decode "
                             "steps took their input cache")
    with clock.phase("decode_memory_analysis"):
        tok, cache = jax.eval_shape(eng.prefill_fn, eng.params,
                                    {"tokens": prompts})[:2]
        mem = eng.decode_fn.lower(eng.params, cache,
                                  tok).compile().memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    k_bytes = max(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(cache))
    print(f"serve: decode alias_bytes={mem.alias_size_in_bytes} "
          f"temp_bytes={mem.temp_size_in_bytes} cache_bytes={cache_bytes} "
          f"stacked_bytes={k_bytes} cache_donated={s.cache_donated}")
    if mem.alias_size_in_bytes < cache_bytes:
        raise AssertionError("the decode program does not take its cache "
                             "in place")
    if jax.default_backend() == "tpu" and \
            not mem.temp_size_in_bytes < k_bytes:
        raise AssertionError(f"the decode program's temporaries "
                             f"({mem.temp_size_in_bytes} B) hold a stacked "
                             f"cache ({k_bytes} B)")


def check_kernels(clock, cases, impl="pallas"):
    import jax

    for i, case in enumerate(cases):
        args = case.sample(jax.random.key(i))
        with clock.phase(f"kernel_{case.name}"):
            got = jax.block_until_ready(
                jax.jit(lambda *a: case.op(*a, impl=impl))(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.jit(case.ref)(*args)
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        errs = [_rel_err(g, w) for g, w in zip(got, want)]
        print(f"kernel {case.name}: shapes={[s for s, _ in case.shapes]} "
              f"max_rel_err={max(errs):.3e} (tol {KERNEL_TOL:g})")
        if not max(errs) <= KERNEL_TOL:
            raise AssertionError(f"{case.name} off its oracle by {errs}")


def check_sharded_training(clock, cfg, devices, *, model, batch, seq, steps):
    """Losses of ``steps`` train steps on a (data, model) mesh over
    ``devices`` against the same steps on ``devices[0]`` alone."""
    import jax

    from repro.config import OptimizerConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_sharded_train

    opt_cfg = OptimizerConfig(warmup_steps=max(steps // 10, 1),
                              total_steps=steps)
    k1, k2 = jax.random.split(jax.random.key(1))
    data = {"tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size),
            "targets": jax.random.randint(k2, (batch, seq), 0,
                                          cfg.vocab_size)}

    def run(name, devs, model_axis):
        mesh = make_host_mesh(model=model_axis, devices=devs)
        with clock.phase(f"train_{name}"):
            step_fn, params, opt = build_sharded_train(cfg, mesh, opt_cfg)
            compiled = step_fn.lower(params, opt, data).compile()
            losses = []
            for _ in range(steps):
                params, opt, metrics = compiled(params, opt, data)
                losses.append(float(metrics["loss"]))
        per_dev = {}
        for leaf in jax.tree.leaves(params):
            for shard in leaf.addressable_shards:
                per_dev[shard.device.id] = (per_dev.get(shard.device.id, 0)
                                            + shard.data.nbytes)
        mem = compiled.memory_analysis()
        print(f"train {name}: mesh={dict(mesh.shape)} losses={losses} "
              f"param_bytes_per_device={per_dev} "
              f"argument_bytes={mem.argument_size_in_bytes} "
              f"temp_bytes={mem.temp_size_in_bytes} "
              f"output_bytes={mem.output_size_in_bytes}")
        del params, opt
        return losses, mem.argument_size_in_bytes, per_dev

    sharded, arg_sharded, dev_sharded = run("sharded", devices, model)
    single, arg_single, _ = run("one_chip", devices[:1], 1)
    for i, (a, b) in enumerate(zip(sharded, single)):
        if not abs(a - b) <= LOSS_RTOL * abs(b):
            raise AssertionError(f"step {i}: sharded loss {a} vs {b}")
    if len(dev_sharded) != len(devices) or \
            max(dev_sharded.values()) > 0.75 * sum(dev_sharded.values()):
        raise AssertionError(f"parameters not split: {dev_sharded}")
    if not arg_sharded < 0.75 * arg_single:
        raise AssertionError(f"sharded step takes {arg_sharded} argument "
                             f"bytes per device vs {arg_single} on one")
    print(f"train: losses agree within {LOSS_RTOL:g}; argument bytes per "
          f"device {arg_sharded} sharded vs {arg_single} on one chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training phase on 4 chips")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} compile_cache={cache_dir}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"no TPU found: JAX's devices are {dev.platform}; "
                 "this smoke run is for the chip only")

    from repro.configs import get_config
    from repro.kernels.cases import real_width_cases

    clock = Clock(jax)
    if args.four_chips:
        if len(devices) < 4:
            sys.exit(f"--four-chips needs 4 TPU chips, found {len(devices)}")
        check_sharded_training(clock, get_config("qwen3-0.6b"), devices[:4],
                               model=2, batch=4, seq=256, steps=3)
    else:
        check_serving(clock, "qwen3-0.6b", smoke=False, streams=16, slots=8,
                      prompt_len=1024, gen_len=32)
        check_serving(clock, "granite-moe-1b-a400m", smoke=False, streams=64,
                      slots=32, prompt_len=1024, gen_len=32)
        check_serving(clock, "granite-4.0-h-small", smoke=False, streams=32,
                      slots=16, prompt_len=1024, gen_len=32)
        check_kernels(clock, real_width_cases())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
