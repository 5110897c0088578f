"""Geometry primitives for SWARM: cells, rectangles, clipping.

Space is the unit square [0,1)² discretized into a G×G grid of cells
(paper §4.1.1: "grid cells of a predefined size C1×C2").  Rectangles are
stored *inclusive* in cell coordinates as (r0, c0, r1, c1) with
r0 <= r1, c0 <= c1 — matching the paper's partition borders.

All helpers work on either numpy or jax.numpy arrays (the control plane
uses numpy; the per-tick hot path is jitted).
"""
from __future__ import annotations

import numpy as np


def points_to_cells(xy, grid_size: int):
    """Map float points in [0,1)² to integer cell coords (row, col).

    xy: (..., 2) array with xy[..., 0]=x (col direction), xy[..., 1]=y
    (row direction).  Returns int32 (row, col) clipped into the grid.
    """
    mod = _backend(xy)
    g = grid_size
    col = mod.clip((xy[..., 0] * g).astype(mod.int32), 0, g - 1)
    row = mod.clip((xy[..., 1] * g).astype(mod.int32), 0, g - 1)
    return row, col


def rects_to_cells(rects, grid_size: int):
    """Map float rects (x0, y0, x1, y1) in unit space to inclusive cell
    bounds (r0, c0, r1, c1)."""
    mod = _backend(rects)
    g = grid_size
    c0 = mod.clip((rects[..., 0] * g).astype(mod.int32), 0, g - 1)
    r0 = mod.clip((rects[..., 1] * g).astype(mod.int32), 0, g - 1)
    # Upper bounds: a rect touching x1 covers the cell containing x1.
    c1 = mod.clip((rects[..., 2] * g).astype(mod.int32), 0, g - 1)
    r1 = mod.clip((rects[..., 3] * g).astype(mod.int32), 0, g - 1)
    c1 = mod.maximum(c1, c0)
    r1 = mod.maximum(r1, r0)
    return r0, c0, r1, c1


def boxes_overlap(ar0, ac0, ar1, ac1, br0, bc0, br1, bc1):
    """Inclusive cell-box overlap test; broadcasts."""
    return (ar0 <= br1) & (ar1 >= br0) & (ac0 <= bc1) & (ac1 >= bc0)


def corner_tables(r0, c0, r1, c1, grid_size: int, keys=None,
                  n_keys: int = 1) -> np.ndarray:
    """Dominance counts of inclusive cell boxes, for counting them
    against any query box in O(1) (:func:`count_overlaps`).

    Returns (4, n_keys, G+1, G+1) int64 tables ``T[t, k, i, j]`` = the
    number of boxes of key ``k`` whose corner ``t`` lies at row < i and
    col < j, the corners being (r0, c0), (r0, c1), (r1, c0), (r1, c1).
    ``keys`` (int, in [0, n_keys)) splits the count by key; None counts
    every box under key 0.  Tables of disjoint box sets add.  NumPy
    only: the tables are host state."""
    g1 = grid_size + 1
    k = (np.zeros(len(r0), np.int64) if keys is None
         else np.asarray(keys, np.int64))
    # flat offset of (k, x + 1, y + 1) in a (n_keys, G+1, G+1) table
    rows = [(k * g1 + x + 1) * g1 + 1 for x in (r0, r1)]
    out = np.empty((4, n_keys, g1, g1), np.int64)
    for t, (i, y) in enumerate(((0, c0), (0, c1), (1, c0), (1, c1))):
        hist = np.bincount(rows[i] + y, minlength=n_keys * g1 * g1)
        out[t] = hist.reshape(n_keys, g1, g1).cumsum(1).cumsum(2)
    return out


def count_overlaps(tables: np.ndarray, pr0, pc0, pr1, pc1) -> np.ndarray:
    """(n_keys, P) number of boxes in :func:`corner_tables` that overlap
    each inclusive box (pr0, pc0, pr1, pc1), exact.

    A box misses [pr0..pr1] × [pc0..pc1] iff it lies wholly above
    (r1 < pr0), below (r0 > pr1), left (c1 < pc0) or right (c0 > pc1);
    above and below exclude each other, as do left and right, so
    inclusion–exclusion over the four leaves four corner counts."""
    t00, t01, t10, t11 = tables
    b, d = np.asarray(pr1) + 1, np.asarray(pc1) + 1
    return t00[:, b, d] - t01[:, b, pc0] - t10[:, pr0, d] + t11[:, pr0, pc0]


def clip_box(qr0, qc0, qr1, qc1, pr0, pc0, pr1, pc1):
    """Clip query box to partition box (assumes overlap); broadcasts."""
    mod = _backend(qr0) if hasattr(qr0, "shape") else np
    return (
        mod.maximum(qr0, pr0),
        mod.maximum(qc0, pc0),
        mod.minimum(qr1, pr1),
        mod.minimum(qc1, pc1),
    )


def box_area(r0, c0, r1, c1):
    return (r1 - r0 + 1) * (c1 - c0 + 1)


def point_in_box(pr, pc, r0, c0, r1, c1):
    return (pr >= r0) & (pr <= r1) & (pc >= c0) & (pc <= c1)


def _backend(x):
    """Pick numpy or jax.numpy based on the array type."""
    if isinstance(x, np.ndarray) or np.isscalar(x):
        return np
    import jax.numpy as jnp

    return jnp
