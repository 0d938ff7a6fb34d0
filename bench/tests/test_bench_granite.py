"""The cell ``granite-moe-1b-a400m.chat`` off the chip: its check at the
small size (the program passes it, the model module's float8_e4m3 control
fails it), the control itself, the configuration check that refuses a
program without Granite's published values, and the two readers of the
grouped matmul on a trimmed trace of the cell recorded on a TPU v5e."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib as tl
import calibrate
from benchlib import harness, xtrace

CONFIG = "granite-3.0-1b-a400m-base"
CELL = "granite-moe-1b-a400m.chat"
TRACE = tl.BENCH / "tests" / "data" / "trace_v5e_granite_chat.json"
QWEN_TRACE = tl.BENCH / "tests" / "data" / "trace_v5e_qwen3_chat.json"
V5E = {"bf16_flop_s": 197e12, "hbm_byte_s": 819e9}
# widest logit gap allowed at the small size: over seeds 1..12 on the CPU
# (one call each) the program read 0 to 0.0027 and the control 0.0219 to
# 0.2101
SMALL_GRANITE_LIMIT = 0.012


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tl.small_root(tmp_path_factory.mktemp("bench"))
    f = root / "bench" / "configs" / f"{CONFIG}.json"
    d = json.loads(f.read_text())
    d["limits"]["max_logit_gap"] = SMALL_GRANITE_LIMIT
    f.write_text(json.dumps(d))
    return root


def test_declared_cell_is_correct_at_the_small_size(root):
    """One call (``seconds=0``) of the cell as ``BENCHMARK.json`` declares
    it: the result line of a run, and ``correct`` true."""
    with tl.jax_config_kept():
        res = harness.run(root, CELL, seed=2 ** 40 + 3, seconds=0,
                          trace=False, t0=time.perf_counter(),
                          require_tpu=False, peak=tl.CPU_PEAK,
                          log=lambda s: None)
    assert set(res["metrics"]) == {"out_tok_s", "peak_hbm_gib", "setup_s"}
    assert res["attempted"] == 4
    assert res["compared"]["served_out_of_vocab"]["value"] == 0
    assert res["compared"]["max_logit_gap"]["limit"] == SMALL_GRANITE_LIMIT
    assert res["correct"], res["compared"]


@pytest.mark.parametrize("seed", [3, 6, 12])
def test_fp8_control_fails_at_the_small_size(root, capsys, seed):
    """``calibrate.py`` on the cell: the program's gap under the limit, the
    control's over it (the three seeds of 1..12 whose control read
    lowest)."""
    with tl.jax_config_kept():
        assert calibrate.main(["--workload", CELL, "--seeds", str(seed)],
                              root=root, require_tpu=False) == 0
    (line,) = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert line["program_gap"] <= SMALL_GRANITE_LIMIT < line["control_gap"]
    assert line["served_out_of_vocab"] == 0


def test_calibrate_reads_both_sides_by_configuration(root, capsys):
    """``calibrate.py --config`` finds the file by its name, no cell
    needed, and prints the program's and the control's gaps per seed."""
    with tl.jax_config_kept():
        assert calibrate.main(["--config", CONFIG, "--seeds", "3,4"],
                              root=root, require_tpu=False) == 0
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [3, 4]
    for x in lines:
        assert x["workload"] == f"{CONFIG}.chat"
        assert x["program_gap"] >= 0 and x["control_gap"] >= 0
        assert x["served_out_of_vocab"] == 0


def test_control_holds_the_logits_in_fp8_and_the_reference_is_decoders(
        root):
    """``quant="none"`` is ``decoder.py``'s reference to the bit; the
    control's logits are float8_e4m3 values, one scale per token."""
    cell = harness.load_cell(root, CELL)
    mod, c = cell.model, cell.config["config"]
    w = jax.jit(lambda k: mod.make_weights(c, k))(jax.random.key(1))
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, c["vocab_size"], (2, 12)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = mod.reference_logits(c, w, toks, 4)
        base = mod.decoder.reference_logits(c, w, toks, 4)
        ctl = mod.reference_logits(c, w, toks, 4, quant="fp8")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(base))
    scale = jnp.max(jnp.abs(ctl), -1, keepdims=True) / 448.0
    held = (ctl / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(held * scale), np.asarray(ctl),
                               rtol=1e-6)
    assert float(jnp.abs(ctl - ref).max()) > 0


def test_program_without_the_published_values_is_refused(root, monkeypatch):
    """A program whose granite configuration keeps ``norm_eps`` 1e-5, as the
    one before the published values did, fails the configuration check
    before anything is built."""
    import dataclasses

    import repro.configs as configs

    get = configs.get_smoke_config
    monkeypatch.setattr(configs, "get_smoke_config", lambda a: (
        dataclasses.replace(get(a), norm_eps=1e-5)))
    with tl.jax_config_kept(), pytest.raises(harness.BenchError,
                                             match="norm_eps"):
        harness.prepare(root, CELL, require_tpu=False, peak=tl.CPU_PEAK,
                        log=lambda s: None)


def _run_on(trace_path, cell_root=tl.REPO, cell=CELL):
    events, calls = xtrace.load_json(trace_path)
    c = harness.load_cell(cell_root, cell)
    return c, harness.Run(c, V5E, 0.0, xtrace.reduce(events, calls))


def test_readers_on_the_chip_trace():
    """The trimmed trace: one prefill of 32 x 1024 tokens and four decode
    steps of 32 lanes.  Self times of the kernel's calls and the bank
    copies before them, summed by hand from the trace; needed work from
    the published widths (24 layers, D 1024, F 512, 32 experts, top-8)."""
    cell, run = _run_on(TRACE)
    L, D, F, E, K = 24, 1024, 512, 32, 8
    prefill_s = (0.038043498 + 0.037736264 + 0.037734004     # gmm calls
                 + 0.001126557 + 0.001083226 + 0.001081973)  # bank copies
    decode_s = (0.001613161 + 0.001564462 + 0.001564433
                + 0.004509566 + 0.004340851 + 0.004332435)
    rows_p, rows_d = 32 * 1024 * K, 32 * K
    touched_p = E * (1 - (1 - K / E) ** (32 * 1024))
    touched_d = E * (1 - (1 - K / E) ** 32)
    need_p = max(L * 2 * rows_p * D * 3 * F / 197e12,
                 L * (touched_p * 3 * D * F * 2
                      + rows_p * (3 * D + 3 * F) * 2) / 819e9)
    need_d = max(L * 2 * rows_d * D * 3 * F / 197e12,
                 L * (touched_d * 3 * D * F * 2
                      + rows_d * (3 * D + 3 * F) * 2) / 819e9)
    decode_exec_s = sum(x.seconds for x in run.trace.of_kind("decode"))
    assert decode_exec_s == pytest.approx(0.165203, abs=1e-5)
    got = {n: r.read(run) for n, r in cell.readers.items()}
    assert got["moe_gmm_roofline"] == pytest.approx(
        100 * (need_p + 4 * need_d) / (prefill_s + decode_s), rel=1e-6)
    assert got["moe_decode_share"] == pytest.approx(
        100 * decode_s / decode_exec_s, rel=1e-6)
    assert 0 < got["moe_decode_share"] < got["moe_gmm_roofline"] < 100


def test_readers_read_nothing_without_the_grouped_matmul():
    """On a dense model's trace (qwen3-0.6b.chat, read with granite's
    widths) and on no trace the readers give None, not 0."""
    cell, run = _run_on(QWEN_TRACE)
    for reader in cell.readers.values():
        assert reader.read(run) is None
        assert reader.read(harness.Run(cell, V5E, 0.0, None)) is None
