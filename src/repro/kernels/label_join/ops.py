"""jit'd public wrapper for the label_join kernel (adapts index dtypes).

Pads the query axis up to the sublane multiple (8) and the landmark axis up
to the lane multiple (128) so the label slabs are legal TPU tiles, runs the
masked-intersect kernel, and slices the padding back off. Padded queries and
padded landmark lanes carry all-zero labels, so they contribute neither hits
nor hub candidates — the @pl.when pruned-tile skip removes most of them
outright.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.label_join.kernel import (
    label_join_packed_pallas,
    label_join_pallas,
)
from repro.kernels.mosaic import pick_row_tile, pick_word_tile

_Q_ALIGN = 8    # sublane multiple
_L_ALIGN = 128  # lane multiple


@functools.partial(jax.jit, static_argnames=())
def label_join(out_rows, in_rows):
    """Drop-in replacement for kernels.label_join.ref.label_join_ref
    (bool interface).

    out_rows/in_rows: bool[Q, L] — OUT labels of the Q sources / IN labels
    of the Q destinations -> (hits int32[Q], hub int32[Q]).
    """
    q, l = out_rows.shape
    if q == 0 or l == 0:  # static shapes — resolved at trace time
        return (jnp.zeros((q,), jnp.int32), jnp.full((q,), -1, jnp.int32))
    qpad = -(-q // _Q_ALIGN) * _Q_ALIGN
    lpad = -(-l // _L_ALIGN) * _L_ALIGN
    a = jnp.zeros((qpad, lpad), jnp.int32).at[:q, :l].set(
        out_rows.astype(jnp.int32))
    b = jnp.zeros((qpad, lpad), jnp.int32).at[:q, :l].set(
        in_rows.astype(jnp.int32))
    hits, hub = label_join_pallas(
        a,
        b,
        tq=pick_row_tile(qpad),
        tl=pick_row_tile(lpad),
    )
    return hits[:q], hub[:q]


@functools.partial(jax.jit, static_argnames=())
def label_join_packed(out_words, in_words):
    """Drop-in replacement for label_join_packed_ref (packed interface,
    DESIGN.md §10).

    out_words/in_words: uint32[Q, W] packed label bitsets
    -> (hits int32[Q], hub int32[Q]). Padded queries/words carry zero bits,
    so they contribute neither hits nor hub candidates.
    """
    q, w = out_words.shape
    if q == 0 or w == 0:  # static shapes — resolved at trace time
        return (jnp.zeros((q,), jnp.int32), jnp.full((q,), -1, jnp.int32))
    qpad = -(-q // _Q_ALIGN) * _Q_ALIGN
    a = jnp.zeros((qpad, w), jnp.uint32).at[:q].set(out_words)
    b = jnp.zeros((qpad, w), jnp.uint32).at[:q].set(in_words)
    hits, hub = label_join_packed_pallas(
        a,
        b,
        tq=pick_row_tile(qpad),
        tw=pick_word_tile(w),
    )
    return hits[:q], hub[:q]
