"""The cell ``granite-4.0-h-small.chat`` off the chip: its check at the
small size (the program passes it, ``hybrid_decoder.py``'s float8_e4m3
control fails it), the configuration check that refuses a program without
the published values, the work counts against sums done by hand from the
published widths, and the readers of the Mamba2 states."""

import json
import time

import pytest

import bench_testlib as tl
import calibrate
from benchlib import harness, xtrace

CELL = "granite-4.0-h-small.chat"
CONF = "bench/configs/hybrid/granite-4.0-h-small.json"
# the program's smoke configuration in the file's keys
SMALL = dict(num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=32,
             shared_intermediate_size=48, vocab_size=512,
             num_router_experts=8, num_local_experts=4,
             num_experts_per_tok=3, mamba_d_state=16, mamba_d_head=16,
             mamba_n_heads=8, attention_multiplier=0.25,
             layer_types=["mamba", "mamba", "attention", "mamba"])
# widest logit gap allowed at the small size: over seeds 1..12 on the CPU
# (one call each) the program read 0 to 0.0022 and the control 0.0184 to
# 0.0605
SMALL_HYBRID_LIMIT = 0.01
V5E = {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tl.small_root(tmp_path_factory.mktemp("bench"))
    f = root / CONF
    d = json.loads(f.read_text())
    d["config"].update(SMALL)
    d.update(program_variant="smoke", slots=2)
    d["limits"]["max_logit_gap"] = SMALL_HYBRID_LIMIT
    f.write_text(json.dumps(d))
    return root


def _model():
    cell = harness.load_cell(tl.REPO, CELL)
    return cell.model, cell.config["config"]


def test_declared_cell_is_correct_at_the_small_size(root):
    """One call (``seconds=0``) of the cell as ``BENCHMARK.json`` declares
    it: the result line of a run, and ``correct`` true."""
    with tl.jax_config_kept():
        res = harness.run(root, CELL, seed=2 ** 40 + 5, seconds=0,
                          trace=False, t0=time.perf_counter(),
                          require_tpu=False, peak=tl.CPU_PEAK,
                          log=lambda s: None)
    assert set(res["metrics"]) == {"out_tok_s", "peak_hbm_gib", "setup_s"}
    assert res["attempted"] == 4
    assert res["compared"]["served_out_of_vocab"]["value"] == 0
    assert res["compared"]["max_logit_gap"]["limit"] == SMALL_HYBRID_LIMIT
    assert res["correct"], res["compared"]


@pytest.mark.parametrize("seed", [3, 5, 7])
def test_fp8_control_fails_at_the_small_size(root, capsys, seed):
    """``calibrate.py`` on the cell: the program's gap under the limit, the
    control's over it (the three seeds of 1..12 whose control read
    lowest)."""
    with tl.jax_config_kept():
        assert calibrate.main(["--workload", CELL, "--seeds", str(seed)],
                              root=root, require_tpu=False) == 0
    (line,) = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert line["program_gap"] <= SMALL_HYBRID_LIMIT < line["control_gap"]
    assert line["served_out_of_vocab"] == 0


@pytest.mark.parametrize("field,value", [
    ("rope", True), ("residual_multiplier", None), ("experts_held", 0),
    ("shared_d_ff", 0), ("block_pattern", ("mamba2_moe",)),
])
def test_program_without_the_published_values_is_refused(
        root, monkeypatch, field, value):
    """A program with RoPE, without Granite's residual multiplier, holding
    every expert, without the shared expert, or with Mamba2 layers only,
    fails the configuration check before anything is built."""
    import dataclasses

    import repro.configs as configs

    get = configs.get_smoke_config
    monkeypatch.setattr(configs, "get_smoke_config", lambda a: (
        dataclasses.replace(get(a), **{field: value})))
    with tl.jax_config_kept(), pytest.raises(harness.BenchError,
                                             match=field):
        harness.prepare(root, CELL, require_tpu=False, peak=tl.CPU_PEAK,
                        log=lambda s: None)


def test_top_level_holds_the_published_config_as_cut():
    """The file's top level holds the published config.json with only the
    keys of ``reduced`` changed (their published values in ``published``),
    and ``config``, which the harness reads, holds the same values plus
    the program's own ``torch_dtype`` and ``num_router_experts``."""
    d = json.loads((tl.REPO / CONF).read_text())
    own = {"torch_dtype", "num_router_experts"}
    published = {k: v for k, v in d["config"].items() if k not in own}
    for k, v in published.items():
        assert k in d and d[k] == v and type(d[k]) is type(v), k
    assert sorted(d["published"]) == sorted(d["reduced"])
    for k in d["reduced"]:
        assert d["published"][k] != d[k], k
    spec = json.loads((tl.REPO / "BENCHMARK.json").read_text())
    (entry,) = [c for c in spec["configs"] if c["file"] == CONF]
    assert entry["reduced"] == d["reduced"]
    assert (d["config"]["num_router_experts"]
            == d["published"]["num_local_experts"])
    assert d["layer_types"][:d["num_hidden_layers"]] == (
        ["mamba"] * 5 + ["attention"] + ["mamba"] * 4)


# Published widths: D 4096, 9 Mamba2 layers and 1 attention layer; Mamba2
# inner 8192 = 128 heads x 64, state 128, conv 4; attention 32 heads / 8 KV
# of 128; experts of 768, 9 held of 72, top-10; shared expert 1536;
# V 100352.
# Mamba2 projections 4096*(2*8192 + 2*128 + 128) + 8192*4096 = 102,236,160;
# attention 4096*4096*2 + 4096*1024*2 = 41,943,040.
MAMBA, ATTN = 102_236_160, 41_943_040
# a token through one Mamba2 mixer: projections, the conv over x, B and C
# 2*4*(8192 + 256), the recurrence 5*128*64*128
MAMBA_TOKEN = 2 * MAMBA + 2 * 4 * 8448 + 5 * 128 * 64 * 128
# a token through one MLP: the router 2*4096*72, the shared expert
# 2*3*4096*1536, and 10 * 9/72 routed rows to held experts of 2*3*4096*768
MLP_TOKEN = 2 * 4096 * 72 + 2 * 3 * 4096 * 1536 + 1.25 * 2 * 3 * 4096 * 768
# weights: a Mamba2 mixer's small ones (conv weights and biases 5*8448,
# the gate norm 8192 in bf16, A_log, dt_bias, D 3*128 in f32)
MAMBA_SMALL = 5 * 8448 * 2 + 8192 * 2 + 3 * 128 * 4
# per layer besides the experts: f32 router, shared expert, two norms
MLP_FIXED = 4096 * 72 * 4 + 3 * 4096 * 1536 * 2 + 2 * 4096 * 2
EXPERT = 3 * 4096 * 768 * 2
HEAD = (4096 + 4096 * 100_352) * 2
# a lane's SSM state (f32) and conv state (bf16), 9 layers
STATE = 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
KV_POS = 2 * 8 * 128 * 2                  # one attention layer


def test_decode_counts():
    m, c = _model()
    flops, byts = m.decode_work(c, 16, 1024)
    want = (16 * (9 * MAMBA_TOKEN + 2 * ATTN + 10 * MLP_TOKEN)
            + 16 * (4 * 32 * 128 * 1025 + 2 * 4096 * 100_352))
    assert flops == pytest.approx(want, rel=1e-12)
    touched = 9 * (1 - (1 - 10 / 72) ** 16)   # held experts 16 tokens reach
    want_b = (9 * (MAMBA * 2 + MAMBA_SMALL) + ATTN * 2
              + 10 * (touched * EXPERT + MLP_FIXED) + HEAD + 16 * 4096 * 2
              + 2 * 16 * STATE + 16 * 1025 * KV_POS + 16 * 100_352 * 2)
    assert byts == pytest.approx(want_b, rel=1e-12)
    # the Mamba2 mixers' weights and states: about half the step's bytes
    assert 0.5 < (9 * MAMBA * 2 + 2 * 16 * STATE) / byts < 0.52
    assert m.state_bytes(c, 16) == 2 * 16 * STATE


def test_prefill_counts():
    m, c = _model()
    flops, byts = m.prefill_work(c, 16, 1024)
    tok = 16 * 1024
    want = (tok * (9 * MAMBA_TOKEN + 2 * ATTN + 10 * MLP_TOKEN)
            + 16 * 4 * 32 * 128 * 1024 * 1025 / 2 + 16 * 2 * 4096 * 100_352)
    assert flops == pytest.approx(want, rel=1e-12)
    # the Mamba2 projections: about 71% of the FLOPs
    assert 0.70 < tok * 9 * 2 * MAMBA / flops < 0.71
    touched = 9 * (1 - (1 - 10 / 72) ** tok)
    want_b = (9 * (MAMBA * 2 + MAMBA_SMALL) + ATTN * 2
              + 10 * (touched * EXPERT + MLP_FIXED) + HEAD
              + tok * 4096 * 2 + 16 * STATE + tok * KV_POS
              + 16 * 100_352 * 2)
    assert byts == pytest.approx(want_b, rel=1e-12)


def _reduced(ops, batch=16, n=4):
    """A reduced trace of ``n`` decode executions of 2 ms over ``batch``
    lanes, with these (label, seconds) device ops."""
    execs = [xtrace.Execution("jit_serve_decode", i * 2_000_000,
                              (i + 1) * 2_000_000, kind="decode",
                              batch=batch, held=1024 + i)
             for i in range(n)]
    return xtrace.Reduced(window_s=0.01, busy_s=0.008, devices=1,
                          executions=execs, idle_gaps=[], device_ops=ops,
                          matched=True)


def test_state_readers_count_the_ops_that_write_the_states():
    """The readers sum the decode ops whose result is a layer's (or the
    stacked) float32 SSM state or bfloat16 conv state at the step's batch,
    and nothing else: not prefill's, not another batch's, not another
    dtype's."""
    cell = harness.load_cell(tl.REPO, CELL)
    ops = [("decode:fusion.1 f32[5,16,128,64,128] fusion", 0.004),
           ("decode:fusion.2 f32[16,128,64,128] fusion", 0.002),
           ("decode:fusion.3 bf16[4,16,3,8192] fusion", 0.0005),
           ("decode:fusion.4 bf16[16,3,128] fusion", 0.0001),
           ("decode:fusion.5 bf16[16,128,64,128] fusion", 0.5),
           ("decode:fusion.6 f32[8,128,64,128] fusion", 0.5),
           ("prefill:fusion.7 f32[5,16,128,64,128] fusion", 0.5),
           ("decode:gmm.8 bf16[160,4096] custom-call", 0.5)]
    run = harness.Run(cell, V5E, 0.0, _reduced(ops))
    sec = 0.004 + 0.002 + 0.0005 + 0.0001
    need = 4 * 2 * 16 * STATE / 819e9
    got = {n: r.read(run) for n, r in cell.readers.items()}
    assert got["ssm_state_roofline"] == pytest.approx(100 * need / sec,
                                                      rel=1e-9)
    assert got["ssm_decode_share"] == pytest.approx(100 * sec / 0.008,
                                                    rel=1e-9)


def test_state_readers_on_the_chip_trace():
    """A traced call of the cell on a TPU v5e, trimmed: one prefill of
    16 x 1024 tokens (its ops dropped) and four decode steps of 16 lanes
    (op names cut after their opcode).  Self times summed by hand from the
    trace: the two runs' in-place state updates,
    ``add_dynamic-update-slice_fusion.4`` f32[5,16,128,64,128] and ``.5``
    f32[4,16,128,64,128], and the conv states' slices, updates and async
    copies; needed: 4 steps x each state read and written once."""
    cell = harness.load_cell(tl.REPO, CELL)
    events, calls = xtrace.load_json(tl.BENCH / "tests" / "data"
                                     / "trace_v5e_hybrid_chat.json")
    run = harness.Run(cell, V5E, 0.0, xtrace.reduce(events, calls))
    assert run.trace.matched and len(run.trace.of_kind("decode")) == 4
    states = 0.004091859 + 0.003269993
    conv = (0.000049468 + 0.000038873 + 0.000028385 + 0.000022819
            + 0.000009699 + 0.000009374 + 0.000005859 + 0.000005415
            + 0.000005042 + 0.000005035 + 0.000004033 + 0.000004024
            + 0.000001004)                      # the last: 24 copies
    decode_s = sum(x.seconds for x in run.trace.of_kind("decode"))
    assert decode_s == pytest.approx(0.041367389, abs=1e-6)
    got = {n: r.read(run) for n, r in cell.readers.items()}
    assert got["ssm_state_roofline"] == pytest.approx(
        100 * 4 * 2 * 16 * STATE / 819e9 / (states + conv), rel=1e-4)
    assert got["ssm_decode_share"] == pytest.approx(
        100 * (states + conv) / decode_s, rel=1e-4)
    assert 0 < got["ssm_decode_share"] < got["ssm_state_roofline"] < 100


def test_state_readers_read_nothing_without_the_states():
    """On a dense model's chip trace (qwen3-0.6b.chat, read with the hybrid
    widths), on a trace without state ops and on no trace the readers give
    None, not 0."""
    cell = harness.load_cell(tl.REPO, CELL)
    events, calls = xtrace.load_json(tl.BENCH / "tests" / "data"
                                     / "trace_v5e_qwen3_chat.json")
    runs = [harness.Run(cell, V5E, 0.0, xtrace.reduce(events, calls)),
            harness.Run(cell, V5E, 0.0, _reduced(
                [("decode:gmm.8 bf16[160,4096] custom-call", 0.5)])),
            harness.Run(cell, V5E, 0.0, None)]
    for run in runs:
        for reader in cell.readers.values():
            assert reader.read(run) is None
