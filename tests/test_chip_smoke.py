"""What a chip run relies on, checked off the chip: chip_smoke.py refuses to
run without a TPU, its checks pass at smoke size on the CPU (four virtual
devices for the sharded phase), the compile cache goes where it is told,
and the benchmark parents that fork workers never hold JAX."""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(args, cwd=ROOT, **env):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, timeout=600,
        cwd=cwd, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_refuses_without_tpu():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


_PHASES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import json
    import jax
    import chip_smoke
    from repro.configs import get_smoke_config
    from repro.kernels.cases import real_width_cases

    clock = chip_smoke.Clock(jax)
    chip_smoke.check_serving(clock, "qwen3-0.6b", smoke=True, streams=4,
                             slots=2, prompt_len=1024, gen_len=4)
    chip_smoke.check_serving(clock, "granite-moe-1b-a400m", smoke=True,
                             streams=4, slots=2, prompt_len=64, gen_len=4)
    chip_smoke.check_serving(clock, "granite-4.0-h-small", smoke=True,
                             streams=4, slots=2, prompt_len=64, gen_len=4)
    # the real-width kernel cases at toy shapes, interpreted
    small = {"flash_attention": (((1, 256, 2, 128), "bfloat16"),) * 3,
             "mamba2_ssd": (((1, 256, 2, 64), "float32"),
                            ((1, 256, 2), "float32"),
                            ((1, 256, 64), "float32"),
                            ((1, 256, 64), "float32")),
             "rwkv6_wkv": (((1, 32, 2, 64), "float32"),) * 4
                          + (((2, 64), "float32"),),
             "moe_ragged_gmm": (((200, 256), "bfloat16"),
                                ((4, 256, 128), "bfloat16"),
                                ((4,), "int32")),
             "moe_ragged_gmm_mixtral": (((160, 512), "bfloat16"),
                                        ((4, 512, 256), "bfloat16"),
                                        ((4,), "int32")),
             "moe_combine_granite": (((2048, 128), "bfloat16"),
                                     ((256, 8), "int32"),
                                     ((256, 8), "float32"),
                                     ((3, 32), "int32")),
             "moe_combine_granite4h": (((2560, 256), "bfloat16"),
                                       ((256, 10), "int32"),
                                       ((256, 10), "float32"),
                                       ((3, 9), "int32"))}
    for name in ("ring_decode_granite", "ring_decode_qwen3"):
        small[name] = (((2, 4, 128), "bfloat16"),
                       ((2, 2, 64, 128), "bfloat16"),
                       ((2, 2, 64, 128), "bfloat16"), ((), "int32"),
                       ((4, 64), "int32"), ((2, 1, 128), "bfloat16"),
                       ((2, 1, 128), "bfloat16"), ((1,), "int32"))
    cases = [dataclasses.replace(c, shapes=small[c.name])
             for c in real_width_cases()]
    chip_smoke.check_kernels(clock, cases, impl="interpret")
    chip_smoke.check_sharded_training(
        clock, get_smoke_config("qwen3-0.6b"), jax.devices(), model=2,
        batch=4, seq=32, steps=3)
    print(json.dumps({"done": True}))
""")


def test_phases_pass_at_smoke_size():
    proc = _run(["-c", _PHASES], PYTHONPATH="src")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert json.loads(proc.stdout.strip().splitlines()[-1]) == {"done": True}
    assert "phase train_sharded" in proc.stdout
    assert "phase prefill_vs_forward" in proc.stdout
    assert "serve: moe_rows=1088 (no drop: 1088)" in proc.stdout
    # the hybrid: 4 streams x (64 + 4) tokens x top-3 x 4 layers, of which
    # the rows routed to the 4 experts held elsewhere are counted only
    assert "(no drop: 3264) moe_rows_elsewhere=" in proc.stdout
    assert proc.stdout.count("serve: decode alias_bytes=") == 3


_CACHE_DIR = textwrap.dedent("""
    import json
    import jax
    from repro.launch.compile_cache import use_compile_cache
    where = use_compile_cache()
    jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
    print(json.dumps([where, jax.config.jax_compilation_cache_dir]))
""")


def test_compile_cache_goes_where_the_environment_says(tmp_path):
    proc = _run(["-c", _CACHE_DIR], PYTHONPATH="src",
                JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    where = json.loads(proc.stdout.strip().splitlines()[-1])
    assert where == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())       # the compile landed there


def test_compile_cache_defaults_to_the_checkout():
    code = _CACHE_DIR.replace("jax.jit(", "# jax.jit(")   # write nothing
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env={**env, "JAX_PLATFORMS": "cpu", "PYTHONPATH": "src"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    where = json.loads(proc.stdout.strip().splitlines()[-1])
    assert where == [str(ROOT / ".jax_cache")] * 2


def test_serve_cli_runs_the_smoke_config():
    proc = _run(["-m", "repro.launch.serve", "--smoke", "--streams", "3",
                 "--slots", "2", "--prompt-len", "8", "--gen-len", "3"],
                PYTHONPATH="src")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "arch=qwen3-0.6b streams=3 slots=2" in proc.stdout
    assert "parked: 1" in proc.stdout       # the third stream waited a wave
    assert proc.stdout.count("stream ") == 3


def test_benchmark_parents_do_not_import_jax():
    """benchmarks/run.py and scale_bench.py fork worker pools; a parent
    that had loaded JAX would hold the chip its children need."""
    code = ("import sys; from benchmarks import run, scale_bench; "
            "run._suites(); print('jax' in sys.modules)")
    proc = _run(["-c", code], PYTHONPATH="src:.")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "False"
