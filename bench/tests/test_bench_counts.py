"""The work counts the rooflines and mfu divide by, against sums done by
hand from the published widths in the configuration files."""

import importlib.util
import json

import pytest

import bench_testlib as tl


def _model(name):
    conf = json.loads((tl.BENCH / "configs" / f"{name}.json").read_text())
    path = tl.BENCH / "configs" / f"{conf['model']}.py"
    spec = importlib.util.spec_from_file_location("bench_model_" + name
                                                  .replace("-", "_")
                                                  .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, conf["config"]


# qwen3-0.6b: D 1024, H 16, KV 8, Dh 128, F 3072, V 151936, L 28, bf16.
# per layer: wq 1024*2048 + wk, wv 1024*1024 + wo 2048*1024 = 6,291,456;
# MLP 3*1024*3072 = 9,437,184; together 15,728,640 weights a token uses.
# LM head 1024*151936 = 155,582,464.  K+V per position 28*2*8*128*2 B =
# 114,688 B.
QWEN_DECODE_FLOPS_B1_H0 = 28 * (2 * 15_728_640 + 4 * 16 * 128 * 1) \
    + 2 * 155_582_464                                   # 1,192,198,144
# weights 28 * (6,291,456 + 2*1024 + 2*128 + 9,437,184) * 2 = 880,932,864,
# final norm and LM head (1024 + 155,582,464) * 2 = 311,166,976, 16
# embedding rows 16*1024*2 = 32,768; KV 16 lanes * 1025 positions *
# 114,688; logits 16 * 151936 * 2
QWEN_DECODE_BYTES_B16_H1024 = 880_932_864 + 311_166_976 + 32_768 \
    + 16 * 1025 * 114_688 + 16 * 151_936 * 2            # 3,077,877,760


def test_qwen_decode():
    m, c = _model("qwen3-0.6b")
    flops, _ = m.decode_work(c, 1, 0)
    assert flops == QWEN_DECODE_FLOPS_B1_H0 == 1_192_198_144
    _, byts = m.decode_work(c, 16, 1024)
    assert byts == QWEN_DECODE_BYTES_B16_H1024 == 3_077_877_760


def test_qwen_prefill():
    m, c = _model("qwen3-0.6b")
    flops, byts = m.prefill_work(c, 16, 1024)
    # 16*1024 tokens through 15,728,640 weights a layer, causal attention
    # 4*16*128 * 1024*1025/2 a row and layer, the LM head once a row
    want = 28 * (2 * 16 * 1024 * 15_728_640
                 + 16 * 4 * 16 * 128 * 1024 * 1025 // 2) \
        + 16 * 2 * 155_582_464
    assert flops == want == 16_362_093_150_208
    # weights once (16*1024 embedding rows), KV of 16*1024 positions,
    # logits of 16 rows
    want_b = (880_932_864 + 311_166_976 + 16 * 1024 * 1024 * 2
              + 16 * 1024 * 114_688 + 16 * 151_936 * 2)
    assert byts == want_b


# granite-moe-1b-a400m: D 1024, H 16, KV 8, Dh 64, expert F 512, E 32,
# top-8, V 49155, L 24.  Attention 1024*1024*2 + 1024*512*2 = 3,145,728;
# a token's MLP 8*3*1024*512 + router 1024*32 = 12,615,680.
def test_granite_prefill_small():
    m, c = _model("granite-moe-1b-a400m")
    flops, byts = m.prefill_work(c, 1, 4)
    assert flops == 24 * (2 * 4 * (3_145_728 + 12_615_680)
                          + 4 * 16 * 64 * 4 * 5 // 2) \
        + 2 * 1024 * 49_155 == 3_127_842_816
    # 4 tokens touch 32 * (1 - 0.75**4) = 21.875 experts on average:
    # 21.875 * 3*1024*512*2 + the f32 router 1024*32*4 = 68,943,872 B a
    # layer, attention and norms (3,145,728 + 2*1024) * 2 = 6,295,552
    per_layer = 68_943_872 + 6_295_552
    want = (24 * per_layer + (1024 + 1024 * 49_155) * 2 + 4 * 1024 * 2
            + 4 * 24 * 2 * 8 * 64 * 2 + 49_155 * 2)
    assert byts == pytest.approx(want, rel=1e-12)
    assert want == 1_906_720_774


def test_granite_decode_reads_every_expert_at_32_lanes():
    m, c = _model("granite-moe-1b-a400m")
    _, b32 = m.decode_work(c, 32, 1024)
    experts = 24 * 32 * 3 * 1024 * 512 * 2          # 2.42 GB
    kv = 32 * 1025 * 24 * 2 * 8 * 64 * 2
    rest = (24 * (1024 * 32 * 4 + (3_145_728 + 2 * 1024) * 2)
            + (1024 + 1024 * 49_155) * 2 + 32 * 1024 * 2 + 32 * 49_155 * 2)
    assert b32 == pytest.approx(experts * (1 - 0.75 ** 32) + kv + rest,
                                rel=1e-12)
    assert b32 == pytest.approx(experts + kv + rest, rel=1e-4)
