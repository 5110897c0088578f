"""Pallas TPU kernel: SWARM Algorithm 2 (round close) for all partitions.

The paper's O(n) "carry the summation" pass *is* a prefix sum.  One grid
step processes a tile of P_TILE partitions with the full statistics row
resident in VMEM ((NUM_CH, P_TILE, G1) ≈ 8·8·1024·4 B = 256 KiB for
G=1000), fusing the three prefix sums and all five channel updates into
a single HBM round-trip — 8 reads + 8 writes per element instead of the
22 a naive per-equation implementation performs.

Mosaic has no ``cumsum`` lowering, so the prefix sum is blocked along
the lanes: within each 128-lane block it is a matmul against an
upper-triangular 0/1 matrix at ``HIGHEST`` (fp32 contract) precision,
and a running (P_TILE, 1) block total carries across blocks — the same
two-level re-association as ``ops.blocked_cumsum``.  It is exact for the
integer-valued collector channels (every partial sum is below 2²⁴).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import C_N, C_Q, C_SPAN, N, NUM_CH, PRESPANQ, Q, R, SPANQ

P_TILE = 8   # partitions per grid step (sublane-friendly)
LANES = 128  # prefix-sum block width (one vreg of lanes)


def _kernel(bank_ref, out_ref, *, decay: float):
    rows, g1 = bank_ref.shape[1:]
    r = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1)
    upper = (r <= c).astype(jnp.float32)     # x @ upper = in-block cumsum
    carry_n = carry_q = carry_s = jnp.zeros((rows, 1), jnp.float32)
    for b in range(g1 // LANES):                       # unrolled, static
        lanes = pl.ds(b * LANES, LANES)

        def scan(ch, carry):
            blk = bank_ref[ch, :, lanes]
            cum = jnp.dot(blk, upper, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32) + carry
            return cum, carry + jnp.sum(blk, axis=1, keepdims=True)

        cum_n, carry_n = scan(C_N, carry_n)
        cum_q, carry_q = scan(C_Q, carry_q)
        span_new, carry_s = scan(C_SPAN, carry_s)
        out_ref[N, :, lanes] = bank_ref[N, :, lanes] * decay + cum_n
        out_ref[Q, :, lanes] = bank_ref[Q, :, lanes] + cum_q
        out_ref[R, :, lanes] = cum_n + cum_q
        out_ref[SPANQ, :, lanes] = bank_ref[SPANQ, :, lanes] + span_new
        out_ref[PRESPANQ, :, lanes] = span_new
        zeros = jnp.zeros_like(cum_n)
        out_ref[C_N, :, lanes] = zeros
        out_ref[C_Q, :, lanes] = zeros
        out_ref[C_SPAN, :, lanes] = zeros


@functools.partial(jax.jit, static_argnames=("decay", "interpret"))
def stats_update_kernel(bank, *, decay: float = 0.5, interpret: bool = False):
    """bank: (NUM_CH, P, G1) f32 with P % P_TILE == 0 and G1 % LANES == 0."""
    _, p, g1 = bank.shape
    return pl.pallas_call(
        functools.partial(_kernel, decay=decay),
        grid=(p // P_TILE,),
        in_specs=[pl.BlockSpec((NUM_CH, P_TILE, g1), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((NUM_CH, P_TILE, g1), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((NUM_CH, p, g1), jnp.float32),
        interpret=interpret,
    )(bank)
