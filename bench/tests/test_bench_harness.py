"""The harness off the chip: it refuses to run without a TPU, finds cells,
configurations, traffic mixes and metric readers by name, and prints the
result line the contract asks for."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import bench_testlib as tl
from benchlib import harness, peaks
from benchlib.traffic import Traffic

COMMAND = json.loads((tl.REPO / "BENCHMARK.json").read_text())["command"]


def _bench(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, *COMMAND[1:], "--workload", "qwen3-0.6b.chat",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("trace", ["0", "1"])
def test_refuses_without_tpu(trace):
    proc = _bench(tl.REPO, "--trace", trace)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "platform=cpu" in proc.stderr and "device_kind=" in proc.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(tl.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tl.BENCH, tmp_path / tl.BENCH.name,
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _bench(tmp_path, "--trace", "0")
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_peaks_are_keyed_by_device_kind():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flop_s"] == 197e12 and v5e["hbm_byte_s"] == 819e9
    with pytest.raises(LookupError):
        peaks.lookup("TPU v4")


def test_every_declared_cell_loads():
    spec = json.loads((tl.REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(tl.REPO, w["name"])
        assert cell.chips == w["chips"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}


def test_seed_uses_every_bit():
    import jax

    a = jax.random.key_data(harness.seed_key(jax, 7))
    b = jax.random.key_data(harness.seed_key(jax, 2 ** 33 + 7))
    assert not np.array_equal(a, b)


def test_traffic_same_seed_same_prompts_other_seed_same_sizes():
    mix = json.loads((tl.BENCH / "traffic" / "chat.json").read_text())
    t1, t2, t3 = (Traffic(mix, slots=16, vocab=151936, seed=s)
                  for s in (2 ** 40 + 1, 2 ** 40 + 1, 2 ** 40 + 2))
    assert np.array_equal(t1.call_prompts(3), t2.call_prompts(3))
    assert not np.array_equal(t1.call_prompts(3), t3.call_prompts(3))
    assert t1.call_prompts(0).shape == t3.call_prompts(5).shape == (32, 1024)
    assert t1.check_sample(4) == t2.check_sample(4)


EXTRA_METRIC = '''
def read(run):
    t = run.trace
    return None if t is None else float(len(t.of_kind("decode")))
'''


def test_added_cell_config_traffic_and_metric_are_found(tmp_path):
    """A later change adds a cell by adding files and entries only."""
    root = tl.small_root(tmp_path)
    bench = root / tl.BENCH.name
    conf = json.loads((bench / "configs" / "qwen3-0.6b.json").read_text())
    conf["name"] = "qwen3-0.6b-copy"
    (bench / "configs" / "qwen3-0.6b-copy.json").write_text(json.dumps(conf))
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix.update(name="short", prompt_len=16, gen_len=4)
    (bench / "traffic" / "short.json").write_text(json.dumps(mix))
    (bench / "metrics" / "decode_steps.py").write_text(EXTRA_METRIC)

    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "qwen3-0.6b-copy", "source": "x",
                            "file": "bench/configs/qwen3-0.6b-copy.json",
                            "reduced": []})
    spec["workloads"].append({"name": "copy.short", "config":
                              "qwen3-0.6b-copy", "traffic": "short",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "decode_steps", "unit": "steps",
                              "better": "higher", "source": "device_trace",
                              "layer": "engine", "moves": "out_tok_s",
                              "workloads": ["copy.short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell(root, "copy.short")
    assert cell.config["name"] == "qwen3-0.6b-copy"
    assert cell.traffic["gen_len"] == 4
    assert set(cell.readers) == {"decode_steps"}

    with tl.jax_config_kept():
        res = harness.run(root, "copy.short", seed=3, seconds=0.1,
                          trace=True, t0=time.perf_counter(),
                          require_tpu=False, peak=tl.CPU_PEAK,
                          log=lambda s: None)
    assert res["correct"], res["compared"]
    # one traced call: 2 waves of 4 decode steps
    assert res["metrics"] == {"decode_steps": {"value": 8.0,
                                               "unit": "steps"}}
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]


def test_result_line(tmp_path):
    root = tl.small_root(tmp_path)
    with tl.jax_config_kept():
        res = harness.run(root, "qwen3-0.6b.chat", seed=2 ** 35 + 1,
                          seconds=0.2, trace=False, t0=time.perf_counter(),
                          require_tpu=False, peak=tl.CPU_PEAK,
                          log=lambda s: None)
    assert set(res) == {"correct", "attempted", "failed", "metrics",
                        "device", "compared"}
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"out_tok_s", "peak_hbm_gib", "setup_s"}
    assert res["attempted"] > 0 and res["attempted"] % 4 == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    json.dumps(res)
