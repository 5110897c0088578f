"""Public jit'd wrapper for the keyword-match kernel: padding and
output slicing (the kernel takes both entity- and bucket-major layouts
itself)."""
import functools

import jax
import jax.numpy as jnp

from ..spatial_match.ops import RECT_PAD
from .keyword_match import TB, TN, TQ, keyword_match_kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def keyword_match(points, pt_masks, rects, sub_masks, *,
                  interpret: bool = False):
    """points (N, 2) f32; pt_masks (N, T) 0/1; rects (Q, 4) f32;
    sub_masks (Q, T) 0/1.

    Returns (deliveries per point (N,) int32, matches per
    subscription (Q,) int32).  Padded points sit at +inf and padded
    subscriptions are empty boxes, so both fail the spatial test
    regardless of their (zero = wildcard) mask padding; the bucket axis
    is zero-padded, which adds no miss terms."""
    n, q = points.shape[0], rects.shape[0]
    pn, pq = (-n) % TN, (-q) % TQ
    pb = (-pt_masks.shape[1]) % TB
    pts = jnp.pad(points.astype(jnp.float32), ((0, pn), (0, 0)),
                  constant_values=jnp.inf)
    pm = jnp.pad(pt_masks.astype(jnp.float32), ((0, pn), (0, pb)))
    pad = jnp.tile(jnp.asarray(RECT_PAD, jnp.float32), (pq, 1))
    rts = jnp.concatenate([rects.astype(jnp.float32), pad], 0)
    sm = jnp.pad(sub_masks.astype(jnp.float32), ((0, pq), (0, pb)))
    pcnt, qcnt = keyword_match_kernel(pts, pm, rts, sm, interpret=interpret)
    return pcnt[0, :n].astype(jnp.int32), qcnt[0, :q].astype(jnp.int32)
