"""End-to-end behaviour tests: the paper's claims at system level, plus the
HLO analyzer that backs the roofline, and the dry-run artifact integrity."""

import json
from pathlib import Path

import pytest

from repro.core.simulator import run_sim

ROOT = Path(__file__).resolve().parents[1]


def test_scalability_collapse_and_gcr_rescue():
    """Paper headline: base locks collapse when oversubscribed; GCR holds."""
    base = run_sim("mcs_spin", 80).throughput_mops
    peak = run_sim("mcs_spin", 16).throughput_mops
    gcr = run_sim("gcr(mcs_spin)", 80).throughput_mops
    numa = run_sim("gcr_numa(mcs_spin)", 80).throughput_mops
    assert peak / max(base, 1e-9) > 50          # collapse
    assert gcr > 100 * base                     # orders-of-magnitude rescue
    assert numa > gcr                           # NUMA on top (paper claim)


def test_gcr_low_contention_overhead_bounded():
    for n in (1, 2, 4):
        b = run_sim("mcs_spin", n).throughput_mops
        g = run_sim("gcr(mcs_spin)", n).throughput_mops
        assert g > 0.85 * b                     # paper: <= ~12% slowdown


def test_waiting_policy_insensitivity_under_gcr():
    """Paper: with GCR the base lock's waiting policy stops mattering."""
    spin = run_sim("gcr(mcs_spin)", 40).throughput_mops
    stp = run_sim("gcr(mcs_stp)", 40).throughput_mops
    assert abs(spin - stp) / max(spin, stp) < 0.1


def test_dryrun_artifacts_complete():
    """Deliverable (e): every (arch x shape) cell compiled on both meshes."""
    from repro.config import cells_for
    from repro.configs import ARCHS, get_config

    expected = set()
    for arch in ARCHS:
        for shape in cells_for(get_config(arch)):
            expected.add(f"{arch}__{shape.name}.json")
    for mesh in ("16x16", "2x16x16"):
        d = ROOT / "experiments" / "dryrun" / mesh
        if not d.exists():
            pytest.skip("dry-run artifacts not generated yet")
        have = {p.name for p in d.glob("*.json")}
        missing = expected - have
        assert not missing, f"{mesh}: missing {sorted(missing)}"
        # integrity: every record has roofline terms + memory analysis
        for p in d.glob("*.json"):
            rec = json.loads(p.read_text())
            assert rec["roofline"]["compute_s"] > 0
            assert rec["memory"]["temp_bytes"] > 0
            assert rec["hlo_flops"] > 0


def test_hlo_analyzer_loop_correction():
    """The roofline walker multiplies scan bodies by trip count (XLA's
    cost_analysis does not - that is the reason the walker exists)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.hlo_analysis import analyze_hlo, normalize_cost_analysis

    def f_scan(x, w):
        def body(c, wl):
            return jnp.tanh(c @ wl), None
        y, _ = jax.lax.scan(body, x, w)
        return y.sum()

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    c = jax.jit(f_scan).lower(x, w).compile()
    walker = analyze_hlo(c.as_text())["flops"]
    xla = normalize_cost_analysis(c.cost_analysis()).get("flops", 0.0)
    expected = 8 * 2 * 64 * 128 * 128
    assert walker >= expected                   # loop-corrected
    assert xla < expected                       # undercounts (body once)
