"""Pallas TPU kernel: batched 2-hop label intersection (DESIGN.md §9).

One reachability-index probe answers Q (src, dst) queries with a single
masked intersect over the landmark axis:

    hits[q] = |{ i : out_label[src_q, i] AND in_label[dst_q, i] }|
    hub[q]  = min such i   (-1 if none)

i.e. the diagonal of the [Q, L] · [L, Q] label product, computed directly as
an elementwise AND + lane reduction — no MXU needed, the whole probe is one
VPU pass over the [Q, L] label slabs. Grid = (q_tiles, l_tiles) with the
landmark axis innermost ("arbitrary" reduction semantics): each [TQ] output
tile is produced once and revisited across landmark tiles.

Pruning pays off here: the canonical-hub pruning of labels.py zeroes most of
the label matrix, so entire [TQ, TL] OUT tiles are all-zero and are skipped
with ``@pl.when`` — the same empty-tile fast path the BFS kernels use for
retired frontiers. A probe over a well-pruned index touches only the few
tiles holding surviving hub bits.

VMEM per program instance (TQ=256, TL=256): 2 label tiles * 256*256 i32
= 512 KiB, plus two [TQ] i32 accumulators — far under the 16 MiB budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import WORD_BITS
from repro.kernels.mosaic import interpret_mode

INT32_MAX = 2**31 - 1  # python int: pallas kernels must not capture tracers


def _label_join_kernel(out_ref, in_ref, hits_ref, hub_ref, *, tl: int):
    li = pl.program_id(1)
    nl = pl.num_programs(1)

    @pl.when(li == 0)
    def _init():
        hits_ref[...] = jnp.zeros_like(hits_ref)
        hub_ref[...] = jnp.full_like(hub_ref, INT32_MAX)

    a = out_ref[...]  # i32[TQ, TL] — OUT-label slice of this landmark tile

    # pruned-tile skip: a landmark tile none of the Q sources kept a label
    # bit in contributes nothing — canonical-hub pruning makes this the
    # common case (labels concentrate on the few high-degree hubs)
    @pl.when(jnp.any(a > 0))
    def _accumulate():
        common = (a > 0) & (in_ref[...] > 0)                  # [TQ, TL]
        hits_ref[...] += jnp.sum(common.astype(jnp.int32), axis=1)
        lane = li * tl + jax.lax.iota(jnp.int32, tl)          # global hub ids
        cand = jnp.where(common, lane[None, :], INT32_MAX)
        hub_ref[...] = jnp.minimum(hub_ref[...], jnp.min(cand, axis=1))

    @pl.when(li == nl - 1)
    def _epilogue():
        hub_ref[...] = jnp.where(hits_ref[...] > 0, hub_ref[...],
                                 jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("tq", "tl", "interpret"))
def label_join_pallas(out_rows, in_rows, *, tq: int = 256, tl: int = 256,
                      interpret: bool | None = None):
    """Batched label intersection. Q % tq == 0 and L % tl == 0.

    out_rows: int32[Q, L] (0/1)   in_rows: int32[Q, L] (0/1)
    Returns (hits int32[Q], hub int32[Q]) — common-landmark count per query
    and the smallest common landmark index (-1 when the intersection is
    empty). Q is the already-padded query-slab height; callers align it to
    the sublane multiple (kernels/label_join/ops.py pads).
    """
    q, l = out_rows.shape
    assert in_rows.shape == (q, l), (out_rows.shape, in_rows.shape)
    assert q % tq == 0 and l % tl == 0, (q, l, tq, tl)
    grid = (q // tq, l // tl)
    return pl.pallas_call(
        functools.partial(_label_join_kernel, tl=tl),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, tl), lambda qi, li: (qi, li)),
            pl.BlockSpec((tq, tl), lambda qi, li: (qi, li)),
        ],
        out_specs=[
            pl.BlockSpec((tq,), lambda qi, li: (qi,)),
            pl.BlockSpec((tq,), lambda qi, li: (qi,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q,), jnp.int32),
            jax.ShapeDtypeStruct((q,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(out_rows, in_rows)


# ----------------------------------------------------------------------------
# Packed-word variant (DESIGN.md §10): labels stored as uint32 bitsets over
# the landmark axis — hits is a popcount of AND-ed words, hub a
# count-trailing-zeros on the lowest set bit. 32x less label traffic.
# ----------------------------------------------------------------------------
def _label_join_packed_kernel(out_ref, in_ref, hits_ref, hub_ref, *, tw: int):
    li = pl.program_id(1)
    nl = pl.num_programs(1)

    @pl.when(li == 0)
    def _init():
        hits_ref[...] = jnp.zeros_like(hits_ref)
        hub_ref[...] = jnp.full_like(hub_ref, INT32_MAX)

    a = out_ref[...]  # uint32[TQ, TW]

    @pl.when(jnp.max((a != jnp.uint32(0)).astype(jnp.int32)) > 0)
    def _accumulate():
        common = a & in_ref[...]
        hits_ref[...] += jnp.sum(
            jax.lax.population_count(common).astype(jnp.int32), axis=1,
            keepdims=True)
        # smallest set bit per word: ctz(x) = popcount(lowbit(x) - 1)
        low = common & (jnp.uint32(0) - common)
        ctz = jax.lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)
        lane0 = (li * tw + jax.lax.broadcasted_iota(
            jnp.int32, common.shape, 1)) * WORD_BITS
        cand = jnp.where(common != jnp.uint32(0), lane0 + ctz, INT32_MAX)
        hub_ref[...] = jnp.minimum(hub_ref[...],
                                   jnp.min(cand, axis=1, keepdims=True))

    @pl.when(li == nl - 1)
    def _epilogue():
        hub_ref[...] = jnp.where(hits_ref[...] > 0, hub_ref[...],
                                 jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("tq", "tw", "interpret"))
def label_join_packed_pallas(out_words, in_words, *, tq: int = 256,
                             tw: int = 128, interpret: bool | None = None):
    """Packed batched label intersection. Q % tq == 0 and W % tw == 0.

    out_words/in_words: uint32[Q, W] — packed OUT labels of the Q sources /
    IN labels of the Q destinations. Returns (hits int32[Q], hub int32[Q])
    with hub the smallest common landmark index (-1 when empty), identical
    to the dense kernel on the unpacked labels. Per-query answers are lane
    reductions, so the kernel writes them as [Q, 1] columns.
    """
    q, w = out_words.shape
    assert in_words.shape == (q, w), (out_words.shape, in_words.shape)
    assert q % tq == 0 and w % tw == 0, (q, w, tq, tw)
    grid = (q // tq, w // tw)
    hits, hub = pl.pallas_call(
        functools.partial(_label_join_packed_kernel, tw=tw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, tw), lambda qi, li: (qi, li)),
            pl.BlockSpec((tq, tw), lambda qi, li: (qi, li)),
        ],
        out_specs=[
            pl.BlockSpec((tq, 1), lambda qi, li: (qi, 0)),
            pl.BlockSpec((tq, 1), lambda qi, li: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
            jax.ShapeDtypeStruct((q, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(out_words, in_words)
    return hits[:, 0], hub[:, 0]
