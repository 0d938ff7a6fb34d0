"""Configuration system: model configs, input shapes, dry-run cells.

Every assigned architecture is expressed as a ``ModelConfig``; the per-arch
files in ``repro/configs/`` instantiate the exact published hyperparameters
plus a reduced ``smoke`` variant for CPU tests.  Input shapes (the assigned
train/prefill/decode/long cells) are ``ShapeSpec`` instances; the dry-run
enumerates ``cells()`` = (arch x shape) with the assignment's skip rules.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple


def pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

# Block kinds a layer can be:
#   "attn"       - GQA attention + dense MLP        (classic transformer)
#   "moe"        - GQA attention + mixture-of-experts MLP
#   "mamba2"     - Mamba2 (SSD) block
#   "mamba2_moe" - Mamba2 (SSD) mixer + mixture-of-experts MLP
#   "rwkv6"      - RWKV6 block (time mix + channel mix)
BLOCK_KINDS = ("attn", "moe", "mamba2", "mamba2_moe", "rwkv6")
MOE_KINDS = ("moe", "mamba2_moe")


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int                   # query heads (0 => attention-free)
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    block_pattern: Tuple[str, ...] = ("attn",)   # cycled over layers

    # attention details
    d_head: int = 0                # 0 => d_model // n_heads
    qk_norm: bool = False
    rope_theta: float = 1e4
    rope: bool = True              # False: no position embedding (NoPE)
    sliding_window: int = 0        # 0 = full attention

    # MoE
    n_experts: int = 0             # the router's outputs
    n_experts_active: int = 0
    moe_d_ff: int = 0
    # experts 0 .. experts_held-1 are held (and computed) here, the chip's
    # share under expert parallelism; 0 = all n_experts
    experts_held: int = 0
    shared_d_ff: int = 0           # a shared SwiGLU expert beside the MoE

    # SSM (Mamba2 / SSD)
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    # Zamba2-style shared attention block applied every k SSM layers
    shared_attn_every: int = 0     # 0 = no shared block

    # RWKV6
    rwkv_head_dim: int = 64

    # encoder-decoder (whisper)
    n_enc_layers: int = 0          # >0 => encoder-decoder
    enc_seq_divisor: int = 1       # enc_len = seq // divisor (conv stride stub)

    # modality frontend stub ([audio]/[vlm] assignment rule)
    frontend: str = "none"         # none | audio_stub | vision_stub
    frontend_dim: int = 0          # dim of the precomputed embeddings
    n_patches: int = 0             # vision_stub: patches prepended to text

    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    # Granite's scalars (None: absent from the layer equations): the
    # embedding is multiplied by ``embedding_multiplier``, attention scores
    # are scaled by ``attention_multiplier`` in place of 1/sqrt(d_head), the
    # attention and MLP outputs by ``residual_multiplier`` before each
    # residual add, and the logits are divided by ``logits_scaling``
    embedding_multiplier: Optional[float] = None
    attention_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None
    logits_scaling: Optional[float] = None

    # ---- derived ---------------------------------------------------------
    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        return self.d_model // max(1, self.n_heads)

    @property
    def vocab_padded(self) -> int:
        """Vocab padded to a multiple of 128 (lane width x model shards)."""
        return pad_to(self.vocab_size, 128)

    @property
    def is_encdec(self) -> bool:
        return self.n_enc_layers > 0

    @property
    def subquadratic(self) -> bool:
        """True if long-context decode is feasible (assignment rule for
        long_500k: SSM / hybrid / sliding-window archs only)."""
        kinds = set(self.layer_kinds())
        if kinds <= {"mamba2", "rwkv6"}:
            return True
        if self.sliding_window > 0:
            return True
        if "mamba2" in kinds or "rwkv6" in kinds:
            return True   # hybrid: attention cache exists but SSM dominates
        return False

    def layer_kinds(self) -> Tuple[str, ...]:
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))

    def layer_runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """(kind, first layer, layers) of each run of same-kind layers, in
        order: one run for a model whose layers are all of one kind."""
        runs: List[list] = []
        for i, kind in enumerate(self.layer_kinds()):
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, i, 1])
        return tuple(tuple(r) for r in runs)

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head)."""
        d, v = self.d_model, self.vocab_padded
        total = v * d                      # embedding
        total += v * d                     # lm head (untied)
        total += d                         # final norm
        hd = self.head_dim
        for kind in self.layer_kinds():
            if kind in ("attn", "moe"):
                attn = d * (self.n_heads * hd) + 2 * d * (self.n_kv_heads * hd) \
                    + (self.n_heads * hd) * d
                if self.qk_norm:
                    attn += 2 * hd
                total += attn + 2 * d      # block norms
                if kind == "attn":
                    total += 3 * d * self.d_ff
                else:
                    total += self._moe_params()
            elif kind in ("mamba2", "mamba2_moe"):
                di, ns, nh = self.d_inner, self.ssm_state, self.ssm_heads
                total += d * (2 * di + 2 * ns + nh)    # in_proj (z,x,B,C,dt)
                total += (di + 2 * ns) * self.ssm_conv  # conv
                total += 2 * nh + di                   # A_log, D, dt_bias? (nh,nh,di gate norm)
                total += di * d                        # out_proj
                total += d                             # block norm
                if kind == "mamba2_moe":
                    total += d + self._moe_params()
            elif kind == "rwkv6":
                total += 6 * d * d                     # r,k,v,w,g,out projections
                total += 2 * d * self.d_ff             # channel mix (k,v)...
                total += 8 * d                         # decay/bonus/mix params (approx)
                total += 2 * d                         # norms
        if self.shared_attn_every:
            hd2 = self.head_dim
            total += self.d_model * (self.n_heads * hd2) * 2 \
                + 2 * self.d_model * (self.n_kv_heads * hd2) \
                + 3 * self.d_model * self.d_ff + 2 * self.d_model
        if self.is_encdec:
            # encoder blocks (attn + mlp) + decoder cross-attn already counted
            enc = self.n_enc_layers * (
                4 * d * d + 3 * d * self.d_ff + 2 * d)
            cross = self.n_layers * (4 * d * d + d)
            total += enc + cross
        if self.frontend != "none":
            total += self.frontend_dim * d
        return total

    def _moe_params(self) -> int:
        """One MoE layer's held experts, router and shared expert."""
        return (self.n_experts_held * 3 * self.d_model * self.moe_d_ff
                + self.d_model * self.n_experts
                + 3 * self.d_model * self.shared_d_ff)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only active experts)."""
        if self.n_experts == 0:
            return self.param_count()
        total = self.param_count()
        moe_layers = sum(1 for k in self.layer_kinds() if k in MOE_KINDS)
        inactive = max(self.n_experts_held - self.n_experts_active, 0)
        total -= moe_layers * inactive * 3 * self.d_model * self.moe_d_ff
        return total


# ---------------------------------------------------------------------------
# Input shapes (the assigned per-arch shape set)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str    # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


@dataclass(frozen=True)
class Cell:
    arch: str
    shape: ShapeSpec

    @property
    def key(self) -> str:
        return f"{self.arch}/{self.shape.name}"


def cells_for(cfg: ModelConfig) -> List[ShapeSpec]:
    """Assignment skip rules (documented in DESIGN.md section 4):
    long_500k only for sub-quadratic archs; decode shapes for all archs
    here (every assigned arch has a decoder)."""
    out = [SHAPES["train_4k"], SHAPES["prefill_32k"], SHAPES["decode_32k"]]
    if cfg.subquadratic:
        out.append(SHAPES["long_500k"])
    return out


# ---------------------------------------------------------------------------
# Training / runtime configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    zero1: bool = True             # shard optimizer state over the data axis
    grad_compression: str = "none"  # none | int8  (cross-pod hop)


@dataclass(frozen=True)
class MeshConfig:
    multi_pod: bool = False
    # single-pod: (data, model) = (16, 16); multi-pod adds pod=2 in front
    data: int = 16
    model: int = 16
    pods: int = 2

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.pods, self.data, self.model) if self.multi_pod \
            else (self.data, self.model)

    @property
    def axes(self) -> Tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod \
            else ("data", "model")

    @property
    def n_devices(self) -> int:
        n = self.data * self.model
        return n * self.pods if self.multi_pod else n


# TPU v5e hardware model for the roofline (per assignment).
@dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu_v5e"
    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # bytes/s per chip
    ici_bw: float = 50e9             # bytes/s per link
    hbm_bytes: float = 16e9
    # 128 MiB of VMEM per chip, but the TPU compiler (libtpu 0.0.34) holds
    # each Pallas kernel to a scoped limit of 16 MiB of it
    vmem_bytes: float = 128 * 2**20


V5E = HardwareSpec()
