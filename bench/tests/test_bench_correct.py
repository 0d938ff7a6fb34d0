"""The check that decides ``correct``, at a size a test run holds: sound
runs of the program pass it, the fp8 control fails it, and so does a run
whose timed path is broken underneath (the harness's look for a chip is
skipped, the rest of the run is driven as on the chip)."""

import time

import pytest

import bench_testlib as tl
from benchlib import harness

CELL = "qwen3-0.6b.chat"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tl.small_root(tmp_path_factory.mktemp("bench"))


def _run(root, seed, **kw):
    with tl.jax_config_kept():
        return harness.run(root, CELL, seed=seed, seconds=0.2, trace=False,
                           t0=time.perf_counter(), require_tpu=False,
                           peak=tl.CPU_PEAK, log=lambda s: None, **kw)


def test_sound_run_is_correct(root):
    res = _run(root, seed=2 ** 40 + 3)
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert res["compared"]["served_out_of_vocab"]["value"] == 0


@pytest.mark.parametrize("seed", [5, 2 ** 33 + 9, 77])
def test_fp8_control_fails(root, seed, monkeypatch):
    """The reference computed in float8_e4m3, in the program's place: its
    first tokens lie further below the float32 reference's best than the
    limit allows."""
    got = {}
    compare = harness.compare

    def with_control(*a, **k):
        got.update(compare(*a, quant="fp8"))
        return got

    monkeypatch.setattr(harness, "compare", with_control)
    res = _run(root, seed=seed)
    assert res["correct"]
    assert got["control_gap"] > tl.SMALL_LIMIT, got


def _broken(monkeypatch, fault):
    import jax.numpy as jnp

    import repro.models as models

    step = models.decode_step

    def decode_step(cfg, params, caches, tokens, **kw):
        logits, new = step(cfg, params, caches, tokens, **kw)
        if fault == "state_unchanged":
            return logits, caches
        if fault == "half_batch":
            half = logits.shape[0] // 2
            return logits.at[half:].set(logits[:half]), new
        if fault == "token_altered":
            top = logits.argmax(-1)
            bump = jnp.take_along_axis(logits, top[..., None], -1) + 1
            alt = (top + 1) % logits.shape[-1]
            # the fourth decode step of every wave
            hit = caches["pos"] == tl.SMALL_TRAFFIC["prompt_len"] + 3
            return jnp.where(hit, jnp.put_along_axis(
                logits, alt[..., None], bump, -1, inplace=False), logits), new
        raise ValueError(fault)

    monkeypatch.setattr(models, "decode_step", decode_step)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    _broken(monkeypatch, fault)
    res = _run(root, seed=11)
    assert not res["correct"], (fault, res["compared"])
