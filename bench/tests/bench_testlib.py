"""What the benchmark's tests share: import paths, and a small copy of the
benchmark whose cells run the program's reduced ("smoke") configurations on
the CPU in seconds."""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the program's smoke configurations, in the configuration files' keys
SMALL = {
    "qwen3-0.6b": dict(num_hidden_layers=4, hidden_size=64,
                       num_attention_heads=4, num_key_value_heads=2,
                       intermediate_size=128, vocab_size=512, head_dim=16),
    "granite-moe-1b-a400m": dict(num_hidden_layers=2, hidden_size=64,
                                 num_attention_heads=4, num_key_value_heads=2,
                                 intermediate_size=64, vocab_size=512,
                                 num_local_experts=8, num_experts_per_tok=2,
                                 attention_multiplier=0.25),
}
SMALL_TRAFFIC = dict(prompt_len=32, gen_len=8, check_requests=4,
                     check_block_rows=4)
# widest logit gap allowed at the small size: over 12 seeds on the CPU,
# sound runs of the small qwen3 read 0 to 0.040 and its fp8 control 0.236
# to 1.09
SMALL_LIMIT = 0.1
CPU_PEAK = {"bf16_flop_s": 1e12, "hbm_byte_s": 1e11}


def small_root(tmp: Path) -> Path:
    """A checkout-like directory: ``BENCHMARK.json`` and the benchmark's
    files, with every configuration at its smoke size, two slots, and the
    chat mix cut to 32-token prompts and 8 tokens."""
    tmp.mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH, tmp / BENCH.name, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for f in (tmp / BENCH.name / "configs").glob("*.json"):
        d = json.loads(f.read_text())
        d["config"].update(SMALL[d["name"]])
        d.update(program_variant="smoke", slots=2)
        d["limits"]["max_logit_gap"] = SMALL_LIMIT
        f.write_text(json.dumps(d))
    for f in (tmp / BENCH.name / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        t.update(SMALL_TRAFFIC)
        f.write_text(json.dumps(t))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


@contextlib.contextmanager
def jax_config_kept():
    """Leave JAX's compilation-cache settings as they were: a run points
    them into its checkout, and other tests share this process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    try:
        yield
    finally:
        for n, v in was.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
