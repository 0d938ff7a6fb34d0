"""The dropless MoE's grouped matmul in a traced call: which device ops are
its work, and the work it needs.  Read by ``metrics/moe_gmm_roofline.py``
and ``metrics/moe_decode_share.py``.

On a TPU the grouped matmul is megablox's Pallas ``gmm``: three custom
calls an MoE layer (gate, up, down), each with a 2-D bfloat16 result as
long as the routed (token, expert) rows, padded to the kernel's row tile.
Before each call XLA slices that layer's expert bank out of the stacked
weights into a buffer of its own (an op whose result is one bank,
``[E, D, F]`` or ``[E, F, D]``); that copy is where the bank is read from
HBM, so it counts as the grouped matmul's work too.
"""

from __future__ import annotations

import re
from typing import Optional

# "<kind>:<instruction> <dtype>[<dims>] <opcode>", as xtrace labels an op
_LABEL = re.compile(r"^(\w+):(\S+) (\w+)\[([\d,]*)\] ([\w\-]+)$")
MAX_ROW_PAD = 512       # the kernel's largest row tile


def _sizes(run):
    return run.cell.model.sizes(run.cell.config["config"])


def _is_gmm_op(s, rows: int, name: str, dtype: str, dims: tuple,
               opcode: str) -> bool:
    bank = {(s["E"], s["D"], s["F"]), (s["E"], s["F"], s["D"])}
    if dtype != "bf16":
        return False
    if opcode == "custom-call":
        return (len(dims) == 2 and dims[1] in (s["D"], s["F"])
                and rows <= dims[0] < rows + MAX_ROW_PAD)
    return dims in bank and ("slice" in name or opcode == "copy")


def self_seconds(run, kind: str) -> Optional[float]:
    """Device self time of the grouped matmul's ops in the traced call's
    ``kind`` ("prefill" or "decode") executions; None when none is found."""
    t = run.trace
    xs = t.of_kind(kind) if t is not None and t.matched else []
    if not xs:
        return None
    s = _sizes(run)
    rows = {x.batch * (x.seq if kind == "prefill" else 1) * s["K"]
            for x in xs}
    if len(rows) != 1:
        return None
    rows = rows.pop()
    total, found = 0.0, False
    for label, sec in t.device_ops:
        m = _LABEL.match(label)
        if not m or m.group(1) != kind:
            continue
        _, name, dtype, dims, opcode = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d)
        if _is_gmm_op(s, rows, name, dtype, dims, opcode):
            total += sec
            found = True
    return total if found else None


def work(run, x, kind: str) -> tuple:
    """(FLOPs, bytes) the grouped matmuls of one execution need, over all
    MoE layers: rows = tokens x k; FLOPs 2 x rows x D x 3F (gate, up,
    down); bytes the expert banks the tokens route to, read once
    (``_experts_touched``, uniform routing), and each call's rows in and
    out, rows x (3D + 3F), in the configuration's dtype."""
    base = getattr(run.cell.model, "decoder", run.cell.model)
    s = _sizes(run)
    tokens = x.batch * (x.seq if kind == "prefill" else 1)
    rows = tokens * s["K"]
    b = base.DTYPE_BYTES[s["dtype"]]
    D, F = s["D"], s["F"]
    flops = s["L"] * 2 * rows * D * 3 * F
    byts = s["L"] * (base._experts_touched(s, tokens) * 3 * D * F * b
                     + rows * (3 * D + 3 * F) * b)
    return float(flops), float(byts)
