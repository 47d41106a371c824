"""Pallas TPU kernel: batched versioned edge writes (the CAS-apply hot spot).

Applies B pre-resolved edge writes (row, col, val, mask) to the adjacency
tiles and bumps the per-row ``ecnt`` counters — the vectorized form of the
paper's { CAS(enxt) ; FetchAndAdd(ecnt) } pair. The *decision* of which ops
fire (EDGE ADDED vs EDGE PRESENT, CAS pass/fail) is made by the engine
(core/ops.py); this kernel is the bandwidth-bound application step.

Grid = (row_tiles,). Each program owns a (TR x V) adjacency stripe in VMEM
and scans the op batch with predicated scalar stores; writes are applied in
lane order so duplicate (row, col) targets resolve to the last lane — the
batch linearization order. ecnt increments accumulate one per fired op
(duplicates included), matching the engine and the oracle.

VMEM: TR=8, V<=8192 -> 64 KiB stripe; op batch arrays are tiny. On real TPU
the stripe copy-in/out is elided by donating buffers at the jit boundary
(the updates are in-place at the XLA level via input_output_aliasing there).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mosaic import interpret_mode


def _edge_update_kernel(rows_ref, cols_ref, vals_ref, mask_ref, adj_in_ref,
                        ecnt_in_ref, adj_ref, ecnt_ref, *, tr: int):
    t = pl.program_id(0)
    b = rows_ref.shape[0]
    row0 = t * tr

    # initialize output stripe from input stripe
    adj_ref[...] = adj_in_ref[...]
    ecnt_ref[...] = ecnt_in_ref[...]

    def body(i, _):
        r = rows_ref[i]
        c = cols_ref[i]
        vmask = mask_ref[i] > 0
        local = r - row0
        in_tile = (local >= 0) & (local < tr) & vmask
        li = jnp.clip(local, 0, tr - 1)

        @pl.when(in_tile)
        def _apply():
            adj_ref[li, c] = vals_ref[i].astype(adj_ref.dtype)
            ecnt_ref[li] = ecnt_ref[li] + 1

        return 0

    jax.lax.fori_loop(0, b, body, 0)


@functools.partial(jax.jit, static_argnames=("tr", "interpret"))
def edge_update_pallas(adj, ecnt, rows, cols, vals, mask, *, tr: int = 8,
                       interpret: bool | None = None):
    """adj uint8[V,V], ecnt int32[V]; rows/cols/vals/mask int32[B].

    Returns (adj', ecnt'). Rows with mask==0 are ignored. Fired ops must have
    in-range rows/cols (engine guarantees).
    """
    v = adj.shape[0]
    assert v % tr == 0
    grid = (v // tr,)
    return pl.pallas_call(
        functools.partial(_edge_update_kernel, tr=tr),
        grid=grid,
        in_specs=[
            pl.BlockSpec(rows.shape, lambda t: (0,)),
            pl.BlockSpec(cols.shape, lambda t: (0,)),
            pl.BlockSpec(vals.shape, lambda t: (0,)),
            pl.BlockSpec(mask.shape, lambda t: (0,)),
            pl.BlockSpec((tr, v), lambda t: (t, 0)),
            pl.BlockSpec((tr,), lambda t: (t,)),
        ],
        out_specs=[
            pl.BlockSpec((tr, v), lambda t: (t, 0)),
            pl.BlockSpec((tr,), lambda t: (t,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(adj.shape, adj.dtype),
            jax.ShapeDtypeStruct(ecnt.shape, ecnt.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(rows, cols, vals, mask, adj, ecnt)


# ----------------------------------------------------------------------------
# Packed-word variant (DESIGN.md §10): each fired op is a masked single-BIT
# set/clear on one uint32 word of the stripe — the row stripe it streams is
# 32x narrower than the dense kernel's.
# ----------------------------------------------------------------------------
def _edge_update_packed_kernel(rows_ref, cols_ref, vals_ref, mask_ref,
                               adj_in_ref, ecnt_in_ref, adj_ref, ecnt_ref,
                               *, tr: int):
    t = pl.program_id(0)
    b = rows_ref.shape[0]
    row0 = t * tr

    adj_ref[...] = adj_in_ref[...]
    ecnt_ref[...] = ecnt_in_ref[...]

    def body(i, _):
        r = rows_ref[i]
        c = cols_ref[i]
        vmask = mask_ref[i] > 0
        local = r - row0
        in_tile = (local >= 0) & (local < tr) & vmask
        li = jnp.clip(local, 0, tr - 1)
        wi = c // 32
        bit = jnp.uint32(1) << (c % 32).astype(jnp.uint32)

        @pl.when(in_tile)
        def _apply():
            cur = adj_ref[li, wi]
            adj_ref[li, wi] = jnp.where(vals_ref[i] > 0, cur | bit,
                                        cur & ~bit)
            ecnt_ref[li] = ecnt_ref[li] + 1

        return 0

    jax.lax.fori_loop(0, b, body, 0)


@functools.partial(jax.jit, static_argnames=("tr", "interpret"))
def edge_update_packed_pallas(adj_packed, ecnt, rows, cols, vals, mask, *,
                              tr: int = 8, interpret: bool | None = None):
    """adj_packed uint32[V, W], ecnt int32[V]; rows/cols/vals/mask int32[B].

    Returns (adj_packed', ecnt'). Same lane-order last-wins semantics as the
    dense kernel; a fired op flips exactly one bit of one word.
    """
    v, w = adj_packed.shape
    assert v % tr == 0
    grid = (v // tr,)
    return pl.pallas_call(
        functools.partial(_edge_update_packed_kernel, tr=tr),
        grid=grid,
        in_specs=[
            pl.BlockSpec(rows.shape, lambda t: (0,)),
            pl.BlockSpec(cols.shape, lambda t: (0,)),
            pl.BlockSpec(vals.shape, lambda t: (0,)),
            pl.BlockSpec(mask.shape, lambda t: (0,)),
            pl.BlockSpec((tr, w), lambda t: (t, 0)),
            pl.BlockSpec((tr,), lambda t: (t,)),
        ],
        out_specs=[
            pl.BlockSpec((tr, w), lambda t: (t, 0)),
            pl.BlockSpec((tr,), lambda t: (t,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(adj_packed.shape, adj_packed.dtype),
            jax.ShapeDtypeStruct(ecnt.shape, ecnt.dtype),
        ],
        interpret=interpret_mode(interpret),
    )(rows, cols, vals, mask, adj_packed, ecnt)
