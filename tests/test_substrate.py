"""Substrate tests: data pipeline, optimizer, checkpoint, runtime, serving."""

import dataclasses
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.config import OptimizerConfig
from repro.configs import get_smoke_config
from repro.data import PrefetchPipeline, SyntheticTokens
from repro.optim import adamw_init, adamw_update, cosine_schedule
from repro.runtime import (HeartbeatMonitor, StragglerMitigator,
                           plan_elastic_mesh)
from repro.serving.engine import Request, SimServeEngine, make_admission

# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


def test_pipeline_deterministic_and_resumable(tmp_path):
    cfg = get_smoke_config("qwen3-0.6b")
    src = SyntheticTokens(cfg, seq_len=16, global_batch=4, seed=7)
    a = src.global_batch_at(5)
    b = src.global_batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # host shards partition the global batch
    shards = [src.host_shard(5, h, 2)["tokens"] for h in range(2)]
    np.testing.assert_array_equal(np.concatenate(shards), a["tokens"])


def test_prefetch_in_order_and_gcr_locked():
    cfg = get_smoke_config("qwen3-0.6b")
    src = SyntheticTokens(cfg, seq_len=8, global_batch=2, seed=1)
    pipe = PrefetchPipeline(src, depth=4, workers=3, use_gcr=True)
    it = iter(pipe)
    got = [next(it)[0] for _ in range(10)]
    pipe.stop()
    assert got == list(range(10))
    # resumability: a restored pipeline continues from the snapshot
    pipe2 = PrefetchPipeline.restore(src, next_batch=42, workers=2)
    it2 = iter(pipe2)
    i, batch = next(it2)
    pipe2.stop()
    assert i == 42
    np.testing.assert_array_equal(batch["tokens"],
                                  src.global_batch_at(42)["tokens"])


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_quadratic():
    params = {"w": jnp.array([2.0, -3.0, 1.5])}
    opt = adamw_init(params)
    cfg = OptimizerConfig(lr=0.1, warmup_steps=5, total_steps=200,
                          weight_decay=0.0, grad_clip=0.0)

    def loss(p):
        return jnp.sum(jnp.square(p["w"]))

    for _ in range(150):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(g, opt, params, cfg)
    assert float(loss(params)) < 1e-2


def test_cosine_schedule_shape():
    lr = [float(cosine_schedule(s, lr=1.0, warmup_steps=10,
                                total_steps=100)) for s in range(101)]
    assert lr[0] < lr[9] <= 1.0 + 1e-6          # warmup
    assert lr[10] >= lr[50] >= lr[100]          # decay
    assert lr[100] >= 0.099                     # min ratio floor


def test_grad_clip_engages():
    params = {"w": jnp.ones(4)}
    opt = adamw_init(params)
    cfg = OptimizerConfig(grad_clip=1.0)
    g = {"w": jnp.full(4, 1e6)}
    _, _, metrics = adamw_update(g, opt, params, cfg)
    assert float(metrics["grad_norm"]) > 1e5    # reported pre-clip


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    state = {"params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
             "opt": {"count": np.int32(3)}}
    for step in [1, 2, 3]:
        mgr.save(step, state, extra={"data_batch": step * 10})
    step, restored, extra = mgr.restore()
    assert step == 3 and extra["data_batch"] == 30
    np.testing.assert_array_equal(restored["params"]["w"],
                                  state["params"]["w"])
    # retention: only the newest two survive
    assert len(list(tmp_path.glob("step_*"))) == 2


def test_checkpoint_atomicity_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_save=True)
    state = {"w": np.zeros((128, 128), np.float32)}
    for step in range(3):
        mgr.save(step, state)
    mgr.wait()
    # every published checkpoint dir has a manifest (publish is rename-last)
    for d in tmp_path.glob("step_*"):
        assert (d / "manifest.json").exists()
    assert not list(tmp_path.glob(".tmp_*"))


def test_checkpoint_elastic_restore_resharded(tmp_path):
    """Restore under explicit shardings (the elastic path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = jax.make_mesh((1,), ("data",))
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = {"w": np.arange(8, dtype=np.float32)}
    mgr.save(1, state)
    sh = {"w": NamedSharding(mesh, P("data"))}
    _, restored, _ = mgr.restore(shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])


# ---------------------------------------------------------------------------
# runtime (fault tolerance)
# ---------------------------------------------------------------------------


def test_heartbeat_failure_plan():
    t = [0.0]
    mon = HeartbeatMonitor([0, 1, 2, 3], timeout_s=10, clock=lambda: t[0])
    t[0] = 5.0
    mon.beat(0)
    mon.beat(1)
    mon.beat(2)
    t[0] = 12.0   # worker 3 silent past timeout
    plan = mon.plan(latest_ckpt_step=400)
    assert plan.dead_workers == [3]
    assert plan.action == "restart_from_checkpoint"
    assert plan.restore_step == 400
    assert plan.new_world == [0, 1, 2]


def test_straggler_demotion_promotes_spare():
    mit = StragglerMitigator([0, 1, 2, 3], spares=[9], threshold=1.5,
                             patience=2)
    swaps = []
    for _ in range(3):
        swaps += mit.observe({0: 1.0, 1: 1.0, 2: 1.0, 3: 5.0})
    assert swaps == [(3, 9)]
    assert 9 in mit.active and 3 not in mit.active


def test_elastic_mesh_plan():
    plan = plan_elastic_mesh(240, model_parallel=16)
    assert plan.mesh_shape == (15, 16)
    assert plan.chips == 240
    with pytest.raises(ValueError):
        plan_elastic_mesh(8, model_parallel=16)


# ---------------------------------------------------------------------------
# serving engine + admission (integration)
# ---------------------------------------------------------------------------


def _workload(n, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt_len=int(rng.integers(128, 512)),
                    gen_len=int(rng.integers(32, 128)), pod=i % 2,
                    arrive_ms=float(rng.uniform(0, 200)))
            for i in range(n)]


def test_serving_gcr_avoids_collapse():
    none = SimServeEngine(make_admission("none", 256)).run(
        _workload(2048), max_ms=300_000)
    gcr = SimServeEngine(make_admission("gcr", 256)).run(
        _workload(2048), max_ms=300_000)
    assert gcr.token_throughput > 20 * none.token_throughput
    assert gcr.completed == 2048          # nobody starves


def test_serving_pod_locality():
    gcr = SimServeEngine(make_admission("gcr", 256)).run(
        _workload(1024), max_ms=300_000)
    pod = SimServeEngine(make_admission("gcr_pod", 256, n_pods=2)).run(
        _workload(1024), max_ms=300_000)
    assert pod.completed == 1024
    assert pod.token_throughput >= 0.95 * gcr.token_throughput


def _small_engine(n_slots=2):
    from repro.models import init_params
    from repro.serving.engine import JaxServeEngine

    cfg = get_smoke_config("qwen3-0.6b")
    params = init_params(cfg, jax.random.key(0))
    return JaxServeEngine(cfg, params, n_slots=n_slots, max_len=24,
                          admission_kind="gcr")


def _prompts(n, seed=0):
    vocab = get_smoke_config("qwen3-0.6b").vocab_size
    return np.random.default_rng(seed).integers(
        0, vocab, (n, 8)).astype(np.int32)


def test_jax_serve_engine_generates():
    eng = _small_engine(n_slots=2)
    out = eng.generate(_prompts(5), gen_len=4)
    assert out.shape == (5, 4)
    assert (out >= 0).all() and (out < eng.cfg.vocab_padded).all()
    # more streams than slots => the GCR queue was exercised
    assert eng.admission.stat_parked > 0


def test_jax_serve_engine_counts_streams_waves_steps_and_reads():
    eng = _small_engine(n_slots=2)
    eng.generate(_prompts(5), gen_len=4)
    # waves of 2, 2 and 1 lanes: one token per lane and step to the host
    s = eng.stats
    assert (s.calls, s.waves, s.decode_steps) == (1, 3, 12)
    assert s.host_reads == (2 + 2 + 1) * 4 == s.tokens_out
    assert s.streams_parked == 5 - 2
    # the simulator's counter keeps its meaning: offers refused, here the
    # queue's head in the first two waves
    assert eng.admission.stat_parked == 2
    eng.generate(_prompts(4, seed=1), gen_len=3)
    assert (s.calls, s.waves, s.decode_steps) == (2, 5, 18)
    assert s.host_reads == 20 + (2 + 2) * 3 == s.tokens_out
    assert s.streams_parked == 3 + (4 - 2)


def test_jax_serve_engine_fits_one_wave_without_parking():
    eng = _small_engine(n_slots=4)
    eng.generate(_prompts(3), gen_len=2)
    s = eng.stats
    assert (s.waves, s.decode_steps, s.host_reads, s.streams_parked) == (
        1, 2, 6, 0)


def _host_loop_reference(eng, prompts, gen_len, waves):
    """The tokens of greedy decode with the argmax on the host: the model's
    own ``prefill``/``decode_step``, jitted, on each wave's lanes, and each
    lane fed its token one at a time."""
    from repro.models import decode_step, prefill

    pre = jax.jit(lambda p, b: prefill(eng.cfg, p, b, max_len=eng.max_len))
    dec = jax.jit(lambda p, c, t: decode_step(eng.cfg, p, c, t))
    out = np.zeros((len(prompts), gen_len), np.int32)
    for lanes in waves:
        logits, cache = pre(eng.params,
                            {"tokens": jnp.asarray(prompts[lanes])})
        for t in range(gen_len):
            best = np.asarray(logits)[:, -1].argmax(-1)
            tok = np.zeros((len(lanes), 1), np.int32)
            for j, sid in enumerate(lanes):
                out[sid, t] = tok[j, 0] = best[j]
            logits, cache = dec(eng.params, cache, jnp.asarray(tok))
    return out


def test_jax_serve_engine_serves_the_host_loops_tokens():
    eng = _small_engine(n_slots=2)
    prompts = _prompts(5, seed=3)
    out = eng.generate(prompts, gen_len=4)
    assert eng.stats.waves == 3
    want = _host_loop_reference(eng, prompts, 4, [[0, 1], [2, 3], [4]])
    np.testing.assert_array_equal(out, want)


def test_jax_serve_engine_frees_each_done_steps_input_cache():
    """Every decode step's input cache is gone once the step is dispatched:
    the step takes it (donated) and updates it in place, and the engine
    counts each dispatch that took its cache."""
    eng = _small_engine(n_slots=2)
    seen = []
    decode = eng.decode_fn

    def decode_fn(params, cache, tok):
        seen.append(cache)
        return decode(params, cache, tok)

    eng.decode_fn = decode_fn
    out = eng.generate(_prompts(2, seed=4), gen_len=5)
    assert out.shape == (2, 5)
    deleted = [all(a.is_deleted() for a in jax.tree.leaves(c)) for c in seen]
    assert deleted == [True] * 5
    assert eng.stats.decode_steps == 5
    assert eng.stats.cache_donated == eng.stats.decode_steps


@pytest.mark.parametrize("arch,window", [("qwen3-0.6b", 0),
                                         ("granite-moe-1b-a400m", 0),
                                         ("mixtral-8x7b", 1024)])
def test_jax_serve_engine_decode_takes_its_cache_in_place(arch, window):
    """The decode program aliases the whole cache to its output and holds no
    stacked K among its temporaries: the scan reads the ring, and the new
    tokens are written into the donated stack after it.  float32 and four
    layers: the CPU backend computes bf16 in float32 (with converted copies
    of the stack), and keeps a relayout of each layer's K and V for the
    attention's dots, which with two layers would reach a stacked K."""
    from repro.models import init_params, prefill
    from repro.serving.engine import JaxServeEngine

    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32",
                              n_layers=4)
    if window:
        cfg = dataclasses.replace(cfg, sliding_window=window)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    eng = JaxServeEngine(cfg, params, n_slots=4, max_len=2048)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 8), jnp.int32)}
    tok, cache = jax.eval_shape(eng.prefill_fn, params, batch)[:2]
    mem = eng.decode_fn.lower(params, cache, tok).compile().memory_analysis()
    k = cache["layers"]["k"]
    if window:
        assert k.shape[2] == window       # the ring holds the window
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < k.size * k.dtype.itemsize


def test_jax_serve_engine_programs_have_stable_names():
    from repro.models import prefill

    eng = _small_engine()
    batch = {"tokens": jnp.asarray(_prompts(2))}
    low = eng.prefill_fn.lower(eng.params, batch)
    assert "module @jit_serve_prefill" in low.as_text()
    _, cache = jax.eval_shape(
        lambda p, b: prefill(eng.cfg, p, b, max_len=24), eng.params, batch)
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    low = eng.decode_fn.lower(eng.params, cache, tok)
    assert "module @jit_serve_decode" in low.as_text()
    # both step programs hand back the greedy token, not the logits
    assert jax.eval_shape(eng.prefill_fn, eng.params, batch)[0] == tok
    assert jax.eval_shape(eng.decode_fn, eng.params, cache, tok)[0] == tok


def _traced_generate(tmp_path, eng, prompts, gen_len):
    """Host events ``(name, start_ns, end_ns, stats)`` of the thread that ran
    one ``generate`` under the profiler, in time order."""
    from pathlib import Path

    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.generate(prompts, gen_len=gen_len)
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns),
                    int(e.start_ns) + int(e.duration_ns), dict(e.stats))
                   for e in line.events]
            if any(n.startswith("serve.") for n, *_ in evs):
                return sorted(evs, key=lambda e: e[1])
    raise AssertionError("no serve.* spans in the trace")


def test_jax_serve_engine_spans_in_the_profilers_trace(tmp_path):
    from repro.serving import engine as E

    eng = _small_engine(n_slots=2)
    eng.generate(_prompts(3), gen_len=2)          # compile outside the trace
    spans = [(n, st) for n, _, _, st in _traced_generate(
        tmp_path, eng, _prompts(3, seed=1), 2) if n.startswith("serve.")]
    names = [n for n, _ in spans]
    assert set(names) == {E.SPAN_GENERATE, E.SPAN_ADMIT, E.SPAN_PREFILL,
                          E.SPAN_READOUT, E.SPAN_DISPATCH, E.SPAN_RELEASE}
    # two waves (2 lanes, then 1) of 2 steps, read back once a wave
    assert names.count(E.SPAN_GENERATE) == 1
    assert names.count(E.SPAN_PREFILL) == names.count(E.SPAN_RELEASE) == 2
    assert names.count(E.SPAN_READOUT) == 2
    assert names.count(E.SPAN_DISPATCH) == 4
    # metadata rides as stats, the name stays exact
    waves = {st["wave"] for n, st in spans if n == E.SPAN_PREFILL}
    assert waves == {2, 3}                        # the engine's 3rd and 4th
    assert sorted((st["wave"], st["step"]) for n, st in spans
                  if n == E.SPAN_DISPATCH) == [(2, 0), (2, 1), (3, 0), (3, 1)]
    # the readout carries the wave's last step
    assert sorted((st["wave"], st["step"]) for n, st in spans
                  if n == E.SPAN_READOUT) == [(2, 1), (3, 1)]


def test_jax_serve_engine_reads_nothing_between_a_waves_dispatches(
        tmp_path):
    from repro.serving import engine as E

    read = "np.asarray(jax.Array)"                # JAX's span of a host copy
    eng = _small_engine(n_slots=2)
    eng.generate(_prompts(3), gen_len=4)          # compile outside the trace
    events = _traced_generate(tmp_path, eng, _prompts(3, seed=2), 4)
    reads = [s for n, s, _, _ in events if n == read]
    for wave in (2, 3):
        dispatch = [(s, e) for n, s, e, st in events
                    if n == E.SPAN_DISPATCH and st["wave"] == wave]
        assert len(dispatch) == 4
        first, last = dispatch[0][0], dispatch[-1][1]
        assert not [s for s in reads if first <= s <= last]
        # the wave's reads come after its last dispatch, in its readout
        (readout,) = [(s, e) for n, s, e, st in events
                      if n == E.SPAN_READOUT and st["wave"] == wave]
        assert last <= readout[0]
        assert any(readout[0] <= s <= readout[1] for s in reads)
