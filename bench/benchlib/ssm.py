"""The Mamba2 layers' decode state in a traced call: which device ops write
it, and the bytes it must move.  Read by ``metrics/ssm_state_roofline.py``
and ``metrics/ssm_decode_share.py``.

A decode step reads each Mamba2 layer's float32 SSM state, ``(B, H, P, N)``,
and its conv state, ``(B, K-1, inner)`` and ``(B, K-1, N)`` in the
configuration's dtype, and writes the new ones over them.  The ops that
produce those states are found by their result: a float32 array whose
trailing dims are an SSM state, or a bfloat16 one whose trailing dims are a
conv state, with or without the stacked-layer dim in front (the program
writes the new state into the stacked cache in place).

On a TPU v5e (granite-4.0-h-small, 16 lanes) those are, per decode
execution: one ``add_dynamic-update-slice_fusion`` per Mamba2 run, result
``f32[5,16,128,64,128]`` and ``f32[4,16,128,64,128]`` (each layer's state
read, updated and written into the stacked cache in place), about 97% of
the time counted; and for the conv states ``dynamic-slice_bitcast_fusion``
(``bf16[16,3,8192]``, ``bf16[16,3,128]``: a layer's states read),
``fusion`` (``bf16[5,16,3,8192]`` and the like: written into the stack)
and the ``copy-start``/``copy-done`` pairs between them.  The read-out
``y = C . h`` is a fusion of its own (``f32[16,128,64]``) that reads each
state again; its result is not a state, so it is not counted.
"""

from __future__ import annotations

from typing import Optional

from benchlib.moe_gmm import _LABEL


def _sizes(run):
    return run.cell.model.sizes(run.cell.config["config"])


def _is_state_op(s, batch: int, dtype: str, dims: tuple) -> bool:
    if dtype == "f32":
        return dims[-4:] == (batch, s["Hm"], s["P"], s["N"])
    if dtype == "bf16":
        return dims[-3:] in {(batch, s["conv"] - 1, s["di"]),
                             (batch, s["conv"] - 1, s["N"])}
    return False


def self_seconds(run) -> Optional[float]:
    """Device self time of the ops that produce the Mamba2 states in the
    traced call's decode executions; None when there are none (a model
    without Mamba2 layers, no trace, or executions of mixed batch)."""
    t = run.trace
    xs = t.of_kind("decode") if t is not None and t.matched else []
    if not xs or "Hm" not in _sizes(run):
        return None
    batches = {x.batch for x in xs}
    if len(batches) != 1:
        return None
    batch, s = batches.pop(), _sizes(run)
    total, found = 0.0, False
    for label, sec in t.device_ops:
        m = _LABEL.match(label)
        if not m or m.group(1) != "decode":
            continue
        dims = tuple(int(d) for d in m.group(4).split(",") if d)
        if _is_state_op(s, batch, m.group(3), dims):
            total += sec
            found = True
    return total if found else None


def state_bytes(run, x) -> float:
    """Bytes decode execution ``x`` must move of the Mamba2 states: each
    read once and written once (the model module's ``state_bytes``)."""
    return run.cell.model.state_bytes(run.cell.config["config"], x.batch)
