"""The dropless MoE's combine as a Pallas TPU kernel: a gather-reduce that
reads each computed expert row once and writes only the (T, D) output.

The expert rows ``ys`` come sorted by expert, and within an expert's group
in token order (the sort is stable and a token picks an expert at most
once).  So the rows a tile of consecutive tokens sends to expert e form one
contiguous slab of ``ys``: rows ``bounds[i, e]`` to ``bounds[i + 1, e]`` for
token tile i.  The grid runs over token tiles; the slab table is a scalar
prefetch.  For each held expert with rows in the tile the kernel DMAs its
slab in chunks of ``CHUNK`` rows (the last one may start earlier, never past
the computed rows) into a VMEM stage, ``PER_BLOCK`` chunks a block, two
blocks in flight: the next block (or the next tile's first) is read while
one is combined.  Rows routed to experts this chip does not hold lie in no
slab and are never read.

A block is combined on the MXU: ``G (tile, block) @ rows (block, D)``,
where G[t, q] is the float32 gate of token t's row if stage row q holds it
and 0 otherwise.  G is split into three bfloat16 parts whose sum is G
exactly, so each product is exact and the sum is accumulated in float32:
the same arithmetic as gating in float32 and summing, in another order.
Stage rows outside their chunk's slab get a zero column; they are finite
(the kernel zeroes the stage once and reads computed rows only), so they
add nothing.

VMEM: the stage (2, PER_BLOCK * CHUNK, D) bfloat16, the float32
accumulator and the double-buffered (TILE, D) output block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128          # tokens a grid step
CHUNK = 16          # rows a DMA: a bfloat16 sublane tile
ROW_ALIGN = 8       # a DMA's first row: the rows' tiling in HBM
PER_BLOCK = 32      # chunks combined by one matmul


def _vmem_bytes(D: int, itemsize: int) -> int:
    """The scoped VMEM the kernel asks for: its stage, accumulator and
    double-buffered output block, twice over for Mosaic's own temporaries
    (the accumulator's value and each matmul's result are as large as the
    accumulator).  Compiled for a v5e, D = 1024 needs 5 MiB of the 6 asked
    for and D = 4096 just under 19 of the 24."""
    stage = 2 * PER_BLOCK * CHUNK * D * itemsize
    acc = TILE * D * 4
    out = 2 * TILE * D * itemsize
    return 2 * (stage + acc + out)


def _split3(g):
    """float32 -> three bfloat16 parts whose float32 sum is ``g``."""
    hi = g.astype(jnp.bfloat16)
    r = g - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _kernel(bounds_ref, ys_hbm, pos_ref, gate_ref, out_ref,
            stage, sems, info, state, acc_ref, *,
            groups, n_tiles, n_rows, k):
    i = pl.program_id(0)
    C, P = CHUNK, PER_BLOCK
    n_valid = bounds_ref[n_tiles * groups + groups - 1]

    def issue(tile, e, m, slot):
        """Start the DMAs of up to P chunks of ``tile``'s slabs into
        ``slot``, from expert e's chunk m on -> (chunks, e, m) after.  A
        slab's chunks start at its first row rounded down to the rows' HBM
        tiling, C rows apart; one past the last row starts earlier."""
        base = tile * groups

        def body(c):
            p, e, m = c
            first = bounds_ref[base + e]
            end = bounds_ref[base + groups + e]
            at = first // ROW_ALIGN * ROW_ALIGN + m * C
            lo = jnp.maximum(at, first)
            some = lo < end

            @pl.when(some)
            def _start():
                s = jnp.minimum(at, n_rows - C)
                pltpu.make_async_copy(
                    ys_hbm.at[pl.ds(pl.multiple_of(s, ROW_ALIGN), C)],
                    stage.at[slot, pl.ds(pl.multiple_of(p * C, C), C)],
                    sems.at[slot]).start()
                k4 = (slot * P + p) * 4
                info[k4] = s
                info[k4 + 1] = lo
                info[k4 + 2] = jnp.minimum(at + C, end)
                info[k4 + 3] = e

            last = at + C >= end
            return (p + some.astype(jnp.int32), jnp.where(last, e + 1, e),
                    jnp.where(last, 0, m + 1))

        return jax.lax.while_loop(
            lambda c: (c[0] < P) & (c[1] < groups), body,
            (jnp.int32(0), e, m))

    def wait(slot, n):
        def body(_, c):
            pltpu.make_async_copy(ys_hbm.at[pl.ds(0, C)],
                                  stage.at[slot, pl.ds(0, C)],
                                  sems.at[slot]).wait()
            return c
        jax.lax.fori_loop(0, n, body, 0)

    def combine(slot, n):
        for p in range(P):
            k3 = (slot * P + p) * 4

            @pl.when((p < n) & (info[k3] + C > n_valid))
            def _mask():        # rows past the computed ones: anything
                rows = stage[slot, pl.ds(p * C, C)]
                r = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
                stage[slot, pl.ds(p * C, C)] = jnp.where(
                    info[k3] + r < n_valid, rows, jnp.zeros_like(rows))

        lane = jax.lax.broadcasted_iota(jnp.int32, (1, P * C), 1)
        part, r = lane // C, lane % C
        src = jnp.full((1, P * C), -2, jnp.int32)
        for p in range(P):
            at = (slot * P + p) * 4
            row = info[at] + r
            ok = (part == p) & (row >= info[at + 1]) \
                & (row < info[at + 2]) & (p < n)
            src = jnp.where(ok, row, src)
        g = jnp.zeros((pos_ref.shape[0], P * C), jnp.float32)
        for j in range(k):
            g = g + jnp.where(pos_ref[:, j:j + 1] == src,
                              gate_ref[:, j:j + 1], 0.0)
        rows = stage[slot]
        acc = acc_ref[...]
        for part_g in _split3(g):
            acc = acc + jnp.dot(part_g, rows,
                                preferred_element_type=jnp.float32)
        acc_ref[...] = acc

    @pl.when(i == 0)
    def _first():
        stage[...] = jnp.zeros_like(stage)
        n, e, m = issue(0, jnp.int32(0), jnp.int32(0), 0)
        state[0], state[1], state[2], state[3] = 0, n, e, m

    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(c):
        slot, n, e, m, _ = c
        more = e < groups       # else the next block is the next tile's
        nxt = jnp.where(more, i, i + 1)
        e1 = jnp.where(more, e, jnp.where(i + 1 < n_tiles, 0, groups))
        n2, e2, m2 = issue(nxt, e1, jnp.where(more, m, 0), 1 - slot)
        wait(slot, n)
        combine(slot, n)
        return 1 - slot, n2, e2, m2, more

    slot, n, e, m, mine = jax.lax.while_loop(
        lambda c: c[4] & (c[1] > 0), step,
        (state[0], state[1], state[2], state[3], jnp.bool_(True)))

    @pl.when(mine & (i + 1 < n_tiles))    # a tile with no rows left
    def _next():
        n2, e2, m2 = issue(i + 1, jnp.int32(0), jnp.int32(0), slot)
        state[1], state[2], state[3] = n2, e2, m2

    @pl.when(~mine)
    def _save():
        state[1], state[2], state[3] = n, e, m

    state[0] = slot
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_combine_fwd(ys, pos, gates, bounds, *, interpret: bool = False):
    """ys: (R, D) bfloat16, rows sorted by expert (R >= CHUNK); pos, gates:
    (T, k), T a multiple of ``TILE``; bounds: (T // TILE + 1, E) int32, the
    slab table -> (T, D) in ys's dtype."""
    R, D = ys.shape
    T, k = pos.shape
    n_tiles, groups = bounds.shape[0] - 1, bounds.shape[1]
    if T != n_tiles * TILE or R < CHUNK or R % ROW_ALIGN:
        raise ValueError(f"{T} tokens in {n_tiles} tiles of {TILE}, {R} "
                         f"rows: need whole tiles, whole row tiles and a "
                         f"chunk")
    row_spec = pl.BlockSpec((TILE, k), lambda i, b: (i, 0))
    kernel = functools.partial(_kernel, groups=groups, n_tiles=n_tiles,
                               n_rows=R, k=k)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), row_spec, row_spec],
            out_specs=pl.BlockSpec((TILE, D), lambda i, b: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, PER_BLOCK * CHUNK, D), ys.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2 * PER_BLOCK * 4,), jnp.int32),
                pltpu.SMEM((4,), jnp.int32),
                pltpu.VMEM((TILE, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, D), ys.dtype),
        # the stage and its DMAs carry from one tile to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(D, ys.dtype.itemsize)),
        interpret=interpret,
    )(bounds.reshape(-1).astype(jnp.int32), ys, pos.astype(jnp.int32),
      gates.astype(jnp.float32))
