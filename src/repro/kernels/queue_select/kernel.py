"""Tiled masked lexicographic argmin — the scheduler's hot loop on TPU.

The paper's policy selectors reduce to: among feasible waiting jobs, find
the one minimizing (priority, index).  For million-job tables this is a
bandwidth-bound 1-D reduction; the kernel streams (score, feasible) tiles
through VMEM keeping the running (best_score, best_index) pair in scratch.

Grid: (num_tiles,) sequential; scratch: two (1,1) i32 cells.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 2**30 - 1  # python literal: inlined into the kernel, not captured


def _select_kernel(score_ref, mask_ref, out_ref, best_s, best_i, *, tile: int,
                   n_valid: int, num_tiles: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        best_s[0, 0] = jnp.int32(BIG)
        best_i[0, 0] = jnp.int32(-1)

    idx = t * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    feasible = (mask_ref[...] != 0) & (idx < n_valid)
    s = jnp.where(feasible, score_ref[...], BIG)
    tile_best = jnp.min(s)
    # first index achieving the tile minimum
    cand = jnp.where(feasible & (s == tile_best), idx, BIG)
    tile_idx = jnp.min(cand)

    better = (tile_best < best_s[0, 0]) & (tile_idx < BIG)
    best_i[0, 0] = jnp.where(better, tile_idx, best_i[0, 0])
    best_s[0, 0] = jnp.where(better, tile_best, best_s[0, 0])

    @pl.when(t == num_tiles - 1)
    def _fin():
        out_ref[0, 0] = best_i[0, 0]
        out_ref[0, 1] = best_s[0, 0]


def queue_select_blocked(scores: jax.Array, feasible: jax.Array, *,
                         tile: int = 1024) -> jax.Array:
    """Compiled lowering for backends without the Pallas TPU path.

    Same two-stage blocked reduction the kernel performs — per-tile
    (min, first-index) then a cross-tile min — expressed as reshaped
    ``jnp`` reductions so XLA:CPU/GPU emit vectorized loops over
    contiguous ``tile``-wide rows.  Bit-identical to
    ``queue_select_reference`` for every input, including the corner
    where a *feasible* entry carries score ``BIG`` (the reference
    returns its index; scores are pinned < ``BIG`` by the callers).
    """
    N = scores.shape[0]
    feas = feasible.astype(bool)
    s = jnp.where(feas, scores, BIG)
    pad = (-N) % tile
    if pad:
        s = jnp.pad(s, (0, pad), constant_values=BIG)
        feas = jnp.pad(feas, (0, pad))
    nt = s.shape[0] // tile
    st = s.reshape(nt, tile)
    best = jnp.min(jnp.min(st, axis=1))
    idx = jnp.arange(s.shape[0], dtype=jnp.int32).reshape(nt, tile)
    cand = jnp.where(feas.reshape(nt, tile) & (st == best), idx, BIG)
    bi = jnp.min(jnp.min(cand, axis=1))
    found = bi < BIG
    return jnp.stack([jnp.where(found, bi, -1).astype(jnp.int32),
                      jnp.where(found, best, BIG).astype(jnp.int32)])


def queue_select_tiled(scores: jax.Array, feasible: jax.Array, *,
                       tile: int = 1024, interpret: bool = False) -> jax.Array:
    """scores i32[N], feasible i32[N] -> i32[2] = (argmin index or -1, min)."""
    N = scores.shape[0]
    pad = (-N) % tile
    if pad:
        scores = jnp.pad(scores, (0, pad), constant_values=BIG)
        feasible = jnp.pad(feasible, (0, pad))
    nt = (N + pad) // tile
    kern = functools.partial(_select_kernel, tile=tile, n_valid=N,
                             num_tiles=nt)
    out = pl.pallas_call(
        kern,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda t: (0, t)),
            pl.BlockSpec((1, tile), lambda t: (0, t)),
        ],
        # the (index, score) pair is two scalars: TPU stores scalars only
        # to SMEM, so the whole (1, 2) output lives there
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 2), jnp.int32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.int32),
                        pltpu.SMEM((1, 1), jnp.int32)],
        interpret=interpret,
    )(scores.reshape(1, -1), feasible.astype(jnp.int32).reshape(1, -1))
    return out[0]
