"""Public jit'd wrapper for the spatial-match kernel: padding and
output slicing (the kernel takes both entity- and coordinate-major
layouts itself)."""
import functools

import jax
import jax.numpy as jnp

from .spatial_match import TN, TQ, spatial_match_kernel

# empty padded rect: x0 = +inf, x1 = -inf never contains anything
RECT_PAD = (jnp.inf, jnp.inf, -jnp.inf, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spatial_match(points, rects, *, interpret: bool = False):
    """points: (N, 2) f32; rects: (Q, 4) f32 (x0, y0, x1, y1).

    Returns (point_counts (N,) int32, query_counts (Q,) int32).
    Padding points at +inf and rects as empty boxes keeps the counts
    exact for the real entries."""
    n, q = points.shape[0], rects.shape[0]
    pts = jnp.pad(points.astype(jnp.float32), ((0, (-n) % TN), (0, 0)),
                  constant_values=jnp.inf)
    pad = jnp.tile(jnp.asarray(RECT_PAD, jnp.float32), ((-q) % TQ, 1))
    rts = jnp.concatenate([rects.astype(jnp.float32), pad], 0)
    pcnt, qcnt = spatial_match_kernel(pts, rts, interpret=interpret)
    return pcnt[0, :n].astype(jnp.int32), qcnt[0, :q].astype(jnp.int32)
