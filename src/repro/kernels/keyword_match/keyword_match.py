"""Pallas TPU kernel: blocked spatial-keyword subscription matching.

Extends the ``spatial_match`` containment sweep with a keyword
conjunction over hashed term buckets.  The textual test is phrased as a
matmul so it runs on the MXU alongside the VPU containment tile:

    miss[n, q] = Σ_t (1 − pmask[n, t]) · smask[q, t]

counts how many of subscription q's buckets tuple n is missing; the
conjunction holds iff ``miss < 0.5`` (masks are exact 0/1 floats).  A
zero subscription mask — no keywords — misses nothing and degrades to
the pure-spatial test.

Layout follows ``spatial_match``: each count is its own pallas_call
with the reduced axis innermost in the grid (the safe TPU accumulation
pattern), the tile is (reduced, counted) with the counted entity on the
128 lanes, and the result is a lane-dense (1, N) row.  The counted
entity enters coordinate/bucket-major ((2, T) points or (4, T) rects,
(Tp, T) masks), the reduced one entity-major ((T, 2), (T, 4), (T, Tp)),
so the mask contraction is a plain (T, Tp) @ (Tp, T) matmul and nothing
is transposed in the kernel.  The bucket axis is padded to the float32
sublane multiple of 8.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..spatial_match.spatial_match import contains

TN = 128   # points per tile (lanes)
TQ = 128   # subscriptions per tile (lanes)
TB = 8     # term-bucket padding multiple (f32 sublanes)


def _point_count_kernel(pts_ref, pmask_ref, rct_ref, smask_ref, out_ref):
    """pts (2, TN), pmask (Tp, TN); rects (TQ, 4), smask (TQ, Tp)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    inside = contains(pts_ref[0:1, :], pts_ref[1:2, :],
                      rct_ref[:, 0:1], rct_ref[:, 1:2],
                      rct_ref[:, 2:3], rct_ref[:, 3:4])       # (TQ, TN)
    miss = jnp.dot(smask_ref[...], 1.0 - pmask_ref[...],
                   preferred_element_type=jnp.float32)
    hit = (inside & (miss < 0.5)).astype(jnp.float32)
    out_ref[...] += jnp.sum(hit, axis=0, keepdims=True)


def _sub_count_kernel(pts_ref, pmask_ref, rct_ref, smask_ref, out_ref):
    """pts (TN, 2), pmask (TN, Tp); rects (4, TQ), smask (Tp, TQ)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    inside = contains(pts_ref[:, 0:1], pts_ref[:, 1:2],
                      rct_ref[0:1, :], rct_ref[1:2, :],
                      rct_ref[2:3, :], rct_ref[3:4, :])       # (TN, TQ)
    miss = jnp.dot(1.0 - pmask_ref[...], smask_ref[...],
                   preferred_element_type=jnp.float32)
    hit = (inside & (miss < 0.5)).astype(jnp.float32)
    out_ref[...] += jnp.sum(hit, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def keyword_match_kernel(points, pmask, rects, smask, *,
                         interpret: bool = False):
    """points (N, 2), pmask (N, Tp), rects (Q, 4), smask (Q, Tp), all
    f32 with N % TN == Q % TQ == Tp % TB == 0.

    Returns (per-point delivery counts (1, N), per-subscription match
    counts (1, Q)) as float32 (exact integers up to 2^24)."""
    n, tp = pmask.shape
    q = rects.shape[0]
    pcnt = pl.pallas_call(
        _point_count_kernel,
        grid=(n // TN, q // TQ),           # inner axis = sub tiles (reduced)
        in_specs=[
            pl.BlockSpec((2, TN), lambda i, j: (0, i)),
            pl.BlockSpec((tp, TN), lambda i, j: (0, i)),
            pl.BlockSpec((TQ, 4), lambda i, j: (j, 0)),
            pl.BlockSpec((TQ, tp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, TN), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(points.T, pmask.T, rects, smask)
    qcnt = pl.pallas_call(
        _sub_count_kernel,
        grid=(q // TQ, n // TN),           # inner axis = point tiles (reduced)
        in_specs=[
            pl.BlockSpec((TN, 2), lambda i, j: (j, 0)),
            pl.BlockSpec((TN, tp), lambda i, j: (j, 0)),
            pl.BlockSpec((4, TQ), lambda i, j: (0, i)),
            pl.BlockSpec((tp, TQ), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, TQ), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, q), jnp.float32),
        interpret=interpret,
    )(points, pmask, rects.T, smask.T)
    return pcnt, qcnt
