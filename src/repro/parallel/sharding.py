"""Sharding rules: map params / batches / caches / optimizer state to the
production mesh (DP x TP (+EP/SP), hierarchical DP across pods).

Scheme (DESIGN.md section 5):

* **DP**: batch over ``data`` (and ``pod`` when multi-pod).
* **TP** over ``model``: attention by flat Q heads (KV is repeated up to the
  query head count in train/prefill - the MaxText "kv replication" trick -
  so one mesh axis shards one dim); MLP column->row; vocab on the model axis
  for both embedding and LM head.
* **EP** over ``model`` for MoE expert banks when the experts held divide
  the axis (granite 32e); otherwise TP inside experts (mixtral 8e on 16
  shards).  A shared expert is sharded as a dense MLP.
* **SP**: the train/prefill residual stream is sharded (dp, model, None) on
  (B, S, D) - Megatron-style sequence parallelism; XLA inserts the
  all-gather/reduce-scatter pairs around attention/MLP.
* **Decode**: batch on ``data`` when divisible; KV caches, lane-dense
  (L, B, T, kv*dh), sharded along the *sequence* dim T on ``model`` (and
  on ``data`` too for batch=1 long-context)
  - a GSPMD-native distributed flash-decode; SSM/WKV states shard heads on
  ``model``.
* **ZeRO-1**: optimizer moments additionally shard their largest replicated
  dim over the DP axes.

Divisibility is always checked; a dim that does not divide its axis stays
replicated (e.g. whisper's 8 heads on a 16-way model axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import MeshConfig, ModelConfig, ShapeSpec


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


class ShardingRules:
    def __init__(self, cfg: ModelConfig, mesh: Mesh,
                 shape: Optional[ShapeSpec] = None,
                 fsdp: bool = True) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.shape = shape
        self.multi_pod = "pod" in mesh.axis_names
        self.dp: Tuple[str, ...] = (("pod", "data") if self.multi_pod
                                    else ("data",))
        self.tp = "model"
        self.dp_size = _axis_size(mesh, self.dp)
        self.tp_size = _axis_size(mesh, self.tp)
        # FSDP: additionally shard large weights over the data axes (their
        # stacked-layer dim when divisible); XLA gathers each layer's slice
        # on demand inside the scan (fully-sharded data parallelism).
        self.fsdp = fsdp
        self.fsdp_min_elems = 1 << 20
        # dp-only policy (perf: EXPERIMENTS.md section Perf, H1): when the
        # per-shard model width would fall under one MXU lane tile (128),
        # tensor parallelism produces sub-tile shards and resharding storms;
        # for TRAIN shapes with batch divisible by the whole mesh, fold the
        # model axis into data parallelism instead (params FSDP-sharded).
        if (shape is not None and shape.kind == "train"
                and cfg.d_model // max(self.tp_size, 1) < 128
                and shape.global_batch % (self.dp_size * self.tp_size) == 0):
            self.dp = tuple(self.dp) + (self.tp,)
            self.dp_size *= self.tp_size
            self.tp = None
            self.tp_size = 1

    # -- helpers -------------------------------------------------------------
    def _maybe(self, dim: int, axis) -> Optional[Any]:
        """axis if dim divides its total size, else None (replicated)."""
        return axis if dim % _axis_size(self.mesh, axis) == 0 else None

    def _batch_axis(self, b: int):
        return self.dp if b % self.dp_size == 0 else None

    def sharding(self, spec: P) -> NamedSharding:
        return NamedSharding(self.mesh, spec)

    # -- parameters -----------------------------------------------------------
    def _param_spec(self, path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
        name = path[-1]
        parent = path[-2] if len(path) >= 2 else ""
        tp = self.tp
        nd = len(shape)

        def spec_from(last_dims: Dict[int, Any]) -> P:
            entries = [None] * nd
            for rel, axis in last_dims.items():
                if axis is not None and shape[nd + rel] % _axis_size(
                        self.mesh, axis) == 0:
                    entries[nd + rel] = axis
            return P(*entries)

        if name == "embed":
            return spec_from({-2: tp})            # vocab-sharded
        if name == "lm_head":
            return spec_from({-1: tp})
        if name == "frontend_proj":
            return spec_from({-1: tp})
        if parent in ("attn", "cross"):
            # Output-dim (column) sharding only when the head count divides
            # the axis, so the flat->(heads, d_head) reshape stays
            # GSPMD-expressible; otherwise shard the input (row) dim - the
            # projection output is then replicated and reshaped locally
            # (avoids involuntary full rematerializations in SPMD).
            heads_ok = self.cfg.n_heads % self.tp_size == 0
            kv_ok = self.cfg.n_kv_heads % self.tp_size == 0
            if name == "wq":
                return spec_from({-1: tp} if heads_ok else {})
            if name in ("wk", "wv"):
                # replicated when kv heads don't divide the axis: the
                # projections are small and the activations then keep their
                # batch/seq sharding (no full-batch regather)
                return spec_from({-1: tp} if kv_ok else {})
            if name == "wo":
                return spec_from({-2: tp} if heads_ok else {})
            return P(*([None] * nd))              # q_norm / k_norm
        if parent in ("mlp", "shared_mlp"):
            if name in ("wi_gate", "wi_up"):
                return spec_from({-1: tp})
            if name == "wo":
                return spec_from({-2: tp})
        if parent == "moe":
            if name == "router":
                return P(*([None] * nd))
            ep = self.cfg.n_experts_held % self.tp_size == 0
            if ep:
                return spec_from({-3: tp})        # expert-parallel bank
            if name in ("wi_gate", "wi_up"):
                return spec_from({-1: tp})
            return spec_from({-2: tp})
        if parent == "mamba":
            if name in ("w_z", "w_x"):
                return spec_from({-1: tp})
            if name in ("conv_x_w", "conv_x_b", "norm_w"):
                return spec_from({-1: tp})
            if name == "out_proj":
                return spec_from({-2: tp})
            return P(*([None] * nd))
        if parent == "rwkv":
            if name in ("w_r", "w_k", "w_v", "w_g", "c_k"):
                return spec_from({-1: tp})
            if name in ("w_o", "c_v"):
                return spec_from({-2: tp})
            return P(*([None] * nd))
        return P(*([None] * nd))                  # norms, scalars, misc

    def _apply_fsdp(self, spec: P, shape: Tuple[int, ...]) -> P:
        """Shard the LEADING (stacked-layer / vocab) dim over dp.

        Never falls through to inner dims: sharding a matmul's contraction
        dim over dp forces XLA to reshard the activations off their batch
        sharding (full-batch regathers inside the layer loop - measured as
        a 10x collective-term regression on deepseek/zamba2 before this
        guard; see EXPERIMENTS.md section Perf)."""
        size = 1
        for d in shape:
            size *= d
        if size < self.fsdp_min_elems or not shape:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        dp_axis = self.dp if self.multi_pod else self.dp[0]
        if entries[0] is None and shape[0] % self.dp_size == 0 \
                and shape[0] > 1:
            entries[0] = dp_axis
            return P(*entries)
        return spec

    def param_specs(self, params_tree) -> Any:
        def f(path, leaf):
            keys = tuple(getattr(p, "key", getattr(p, "idx", None))
                         for p in path)
            spec = self._param_spec(keys, leaf.shape)
            if self.fsdp:
                spec = self._apply_fsdp(spec, leaf.shape)
            return spec
        return jax.tree_util.tree_map_with_path(f, params_tree)

    def param_shardings(self, params_tree):
        return jax.tree.map(self.sharding, self.param_specs(params_tree))

    # -- optimizer state (ZeRO-1) ----------------------------------------------
    def zero1_spec(self, spec: P, shape: Tuple[int, ...]) -> P:
        entries = list(spec) + [None] * (len(shape) - len(spec))
        used = set()
        for e in entries:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a is not None:
                    used.add(a)
        if any(a in used for a in self.dp):
            return P(*entries)   # FSDP already shards over dp
        for i, (e, d) in enumerate(zip(entries, shape)):
            if e is None and d % self.dp_size == 0 and d > 1:
                entries[i] = self.dp if self.multi_pod else self.dp[0]
                break
        return P(*entries)

    def opt_specs(self, params_tree, zero1: bool = True):
        pspecs = self.param_specs(params_tree)

        def f(spec, leaf):
            return self.zero1_spec(spec, leaf.shape) if zero1 else spec
        return jax.tree.map(f, pspecs, params_tree)

    # -- batches ----------------------------------------------------------------
    def batch_specs(self, batch_tree) -> Any:
        def f(leaf):
            b = leaf.shape[0]
            entries = [self._batch_axis(b)] + [None] * (len(leaf.shape) - 1)
            return P(*entries)
        return jax.tree.map(f, batch_tree)

    # -- caches -----------------------------------------------------------------
    def cache_specs(self, cache_tree, batch: int) -> Any:
        """Decode-cache sharding.  Leaves are (L, B, ...) stacked buffers.

        K/V rings (self, zamba2's shared block, whisper's cross cache) are
        (L, B, T, kv*dh): heads and head dim merged into the minor dim, so
        it fills the TPU's 128-lane tiles and one position is a contiguous
        slab (decode writes a token without striding across the ring).
        Batch goes on dp, the sequence axis T on model (and dp at batch 1).
        """
        b_axis = self._batch_axis(batch)

        def f(path, leaf):
            keys = [str(getattr(p, "key", "")) for p in path]
            nd = len(leaf.shape)
            if nd == 0:                       # pos scalar
                return P()
            name = keys[-1]
            entries: list = [None] * nd
            if name in ("k", "v") and nd == 4:
                # (L, B, T, kv*dh): batch on dp; seq on model (+dp if b=1)
                entries[1] = b_axis
                seq_axes = (self.tp if b_axis is not None
                            else (tuple(self.dp) + (self.tp,)))
                entries[2] = self._maybe(leaf.shape[2], seq_axes)
            elif name == "ssm" and nd == 5:    # (L,B,H,P,N)
                entries[1] = b_axis
                entries[2] = self._maybe(leaf.shape[2], self.tp)
            elif name == "wkv" and nd == 5:    # (L,B,H,P,P)
                entries[1] = b_axis
                entries[2] = self._maybe(leaf.shape[2], self.tp)
            elif nd >= 2:                      # shifts, conv states, misc
                entries[1] = b_axis
                if name == "x" and nd == 4:    # mamba conv state (L,B,K,di)
                    entries[3] = self._maybe(leaf.shape[3], self.tp)
            return P(*entries)
        return jax.tree_util.tree_map_with_path(f, cache_tree)

    def cache_shardings(self, cache_tree, batch: int):
        return jax.tree.map(self.sharding,
                            self.cache_specs(cache_tree, batch))

    # -- activation constraints ---------------------------------------------------
    def constrain(self, x, kind: str = "residual"):
        """Pin intermediate activations to the mesh (called by the model)."""
        mesh = self.mesh
        if kind == "residual":
            if x.ndim != 3:
                return x
            b, s, _ = x.shape
            b_axis = self._batch_axis(b)
            s_axis = self._maybe(s, self.tp) if s > 1 else None
            spec = P(b_axis, s_axis, None)
        elif kind == "logits":
            b = x.shape[0]
            spec = P(self._batch_axis(b), None,
                     self._maybe(x.shape[-1], self.tp))
        elif kind == "heads":
            # q/k/v in flat-head layout (B, S, H, D): heads on model
            if x.ndim != 4:
                return x
            spec = P(self._batch_axis(x.shape[0]), None,
                     self._maybe(x.shape[2], self.tp), None)
        else:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))
