"""Pallas TPU kernel: blocked point-in-rectangle spatial join.

TPU adaptation of the paper's per-tuple R*-tree probe (DESIGN.md §3):
instead of pointer-chasing a tree, a dense *blocked* containment test —
a tile of comparisons on the VPU, with points and rectangles staged
through VMEM.  For the partition-local candidate sets SWARM produces
(10²–10⁵ queries), the dense sweep beats a tree: no divergence, full
8×128 vector utilization.

Each count is its own pallas_call with the *reduced* axis as the
innermost grid dimension, so the accumulator tile is revisited on
consecutive grid steps only (the safe TPU accumulation pattern).  The
counted entity always sits on the 128 lanes: the tile is (reduced,
counted), the counted entity's coordinates enter coordinate-major as
(1, T) rows and the reduced entity's entity-major as (T, 1) columns, and
the sum over sublanes leaves a (1, T) row that is stored into a (1, N)
lane-dense output.  Both layouts of both inputs are passed, so no
in-kernel transpose is needed.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TN = 128   # points per tile (lanes)
TQ = 128   # rects per tile (lanes)


def contains(px, py, x0, y0, x1, y1):
    return (px >= x0) & (px <= x1) & (py >= y0) & (py <= y1)


def _point_count_kernel(pts_ref, rct_ref, out_ref):
    """pts (2, TN) coordinate-major, rects (TQ, 4) entity-major."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hit = contains(pts_ref[0:1, :], pts_ref[1:2, :],          # (1, TN)
                   rct_ref[:, 0:1], rct_ref[:, 1:2],          # (TQ, 1)
                   rct_ref[:, 2:3], rct_ref[:, 3:4])
    out_ref[...] += jnp.sum(hit.astype(jnp.float32), axis=0, keepdims=True)


def _query_count_kernel(pts_ref, rct_ref, out_ref):
    """pts (TN, 2) entity-major, rects (4, TQ) coordinate-major."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hit = contains(pts_ref[:, 0:1], pts_ref[:, 1:2],          # (TN, 1)
                   rct_ref[0:1, :], rct_ref[1:2, :],          # (1, TQ)
                   rct_ref[2:3, :], rct_ref[3:4, :])
    out_ref[...] += jnp.sum(hit.astype(jnp.float32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spatial_match_kernel(points, rects, *, interpret: bool = False):
    """points: (N, 2) f32, rects: (Q, 4) f32, N % TN == Q % TQ == 0.

    Returns (point counts (1, N), query counts (1, Q)) as float32 (exact
    integers up to 2^24)."""
    n, q = points.shape[0], rects.shape[0]
    points_t, rects_t = points.T, rects.T
    pcnt = pl.pallas_call(
        _point_count_kernel,
        grid=(n // TN, q // TQ),           # inner axis = rect tiles (reduced)
        in_specs=[
            pl.BlockSpec((2, TN), lambda i, j: (0, i)),
            pl.BlockSpec((TQ, 4), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, TN), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(points_t, rects)
    qcnt = pl.pallas_call(
        _query_count_kernel,
        grid=(q // TQ, n // TN),           # inner axis = point tiles (reduced)
        in_specs=[
            pl.BlockSpec((TN, 2), lambda i, j: (j, 0)),
            pl.BlockSpec((4, TQ), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, TQ), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, q), jnp.float32),
        interpret=interpret,
    )(points, rects_t)
    return pcnt, qcnt
