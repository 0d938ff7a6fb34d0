"""Public op: grouped expert matmuls over rows sorted by expert, with one
count per expert (the dropless MoE): megablox's ``gmm`` on a TPU,
``jax.lax.ragged_dot`` elsewhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import megablox

from .. import interpret_mode

MAX_TILE_M = 512
MIN_TILE_M = 16      # a bf16 sublane tile
MAX_TILE_KN = 1024


def row_tile(rows: int, groups: int) -> int:
    """The kernel's row tile: the power of two at or above a group's mean
    row count, within [MIN_TILE_M, MAX_TILE_M].  Small groups (decode) then
    compute few padding rows, large ones (prefill) take full MXU tiles."""
    per = -(-rows // groups)
    tm = MIN_TILE_M
    while tm < min(per, MAX_TILE_M):
        tm *= 2
    return tm


def ragged_matmul(x, w, group_sizes, *, impl: str = "auto"):
    """x: (M, K), rows sorted by group; w: (G, K, N); group_sizes: int32[G]
    summing to M -> (M, N) in x's dtype, row i times the weights of its
    group, accumulated in float32.

    impl: ``auto`` is megablox's Pallas ``gmm`` on a TPU and
    ``jax.lax.ragged_dot`` on any other backend (XLA's own ragged op, not
    the interpreter); ``pallas`` | ``interpret`` force the kernel, ``ref``
    the ``ragged_dot`` path.  The kernel pads M to its row tile with rows in
    no group and slices them off."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl == "ref":
        return jax.lax.ragged_dot(x, w, group_sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(x.dtype)
    M, K = x.shape
    N = w.shape[-1]
    tm = row_tile(M, w.shape[0])
    pad = -M % tm
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    tiling = (tm, min(K, MAX_TILE_KN), min(N, MAX_TILE_KN))
    # positional: megablox's custom VJP keeps dtype, tiling, transpose and
    # interpret as static arguments
    out = megablox.gmm(xp, w, group_sizes.astype(jnp.int32), x.dtype,
                       tiling, None, None, False, interpret_mode(impl))
    return out[:M] if pad else out
