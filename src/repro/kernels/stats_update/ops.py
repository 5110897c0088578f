"""Public wrappers for the Algorithm-2 round close.

* :func:`close_round` — the Pallas TPU kernel: pad (P, G1) to tile
  multiples, run the kernel, slice.
* :func:`close_round_xla` — portable XLA twin for non-TPU backends.
  XLA:CPU lowers one long ``cumsum`` as a serial scan; re-associating it
  into a two-level (blocks × width) scan keeps the inner pass
  vectorized and the whole fold fuses into a single executable.  The
  re-association is exact for the integer-valued collector channels
  (every partial sum is below 2²⁴), which is what the control plane
  feeds it.

Both return the full updated (NUM_CH, P, G1) bank with collectors
zeroed — the contract ``streaming.planes.JaxPlane.close_round`` builds
on.
"""
import functools

import jax
import jax.numpy as jnp

from .ref import C_N, C_Q, C_SPAN, N, NUM_CH, PRESPANQ, Q, R, SPANQ
from .stats_update import LANES, P_TILE, stats_update_kernel

__all__ = ["close_round", "close_round_inputs", "close_round_xla",
           "blocked_cumsum", "IN_CH", "OUT_CH", "NUM_CH"]


@functools.partial(jax.jit, static_argnames=("decay", "interpret"))
def close_round(bank, *, decay: float = 0.5, interpret: bool = False):
    """Algorithm 2 for one stats bank (NUM_CH, P, G1); any P/G1."""
    _, p, g1 = bank.shape
    pp = (-p) % P_TILE
    pg = (-g1) % LANES
    padded = jnp.pad(bank.astype(jnp.float32), ((0, 0), (0, pp), (0, pg)))
    out = stats_update_kernel(padded, decay=decay, interpret=interpret)
    return out[:, :p, :g1]


def blocked_cumsum(x, block: int = 128):
    """Two-level scan along the last axis: exact re-association of
    ``jnp.cumsum`` into within-block scans plus block-offset adds."""
    p, g1 = x.shape
    pad = (-g1) % block
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    nb = (g1 + pad) // block
    xb = xp.reshape(p, nb, block)
    inner = jnp.cumsum(xb, axis=-1)
    offs = jnp.cumsum(inner[:, :, -1], axis=-1)
    offs = jnp.concatenate([jnp.zeros((p, 1), x.dtype), offs[:, :-1]], axis=1)
    return (inner + offs[:, :, None]).reshape(p, nb * block)[:, :g1]


@functools.partial(jax.jit, static_argnames=("decay", "block"))
def close_round_xla(bank, *, decay: float = 0.5, block: int = 128):
    """Portable fused round close for one (NUM_CH, P, G1) bank."""
    cum_n = blocked_cumsum(bank[C_N], block)
    cum_q = blocked_cumsum(bank[C_Q], block)
    span_new = blocked_cumsum(bank[C_SPAN], block)
    zeros = jnp.zeros_like(cum_n)
    out = [None] * NUM_CH
    out[N] = bank[N] * decay + cum_n
    out[Q] = bank[Q] + cum_q
    out[R] = cum_n + cum_q
    out[SPANQ] = bank[SPANQ] + span_new
    out[PRESPANQ] = span_new
    out[C_N] = out[C_Q] = out[C_SPAN] = zeros
    return jnp.stack(out)


# input/output channel orders of :func:`close_round_inputs` — the
# minimal host↔device transfer set for one round close
IN_CH = (N, Q, SPANQ, C_N, C_Q, C_SPAN)    # R/PRESPANQ are fully derived
OUT_CH = (N, Q, R, SPANQ, PRESPANQ)        # collectors reset host-side


@functools.partial(jax.jit, static_argnames=("decay", "block"))
def close_round_inputs(bank6, *, decay: float = 0.5, block: int = 128):
    """Transfer-minimal round close: ``bank6`` holds only the six input
    channels (:data:`IN_CH` order, shape (6, P, G1)); returns the five
    maintained channels (:data:`OUT_CH` order).  Same fold as
    :func:`close_round_xla` — R and preSpanQ' need no input and the
    collector zeroing is a host-side fill."""
    n_in, q_in, spanq_in, c_n, c_q, c_span = bank6
    cum_n = blocked_cumsum(c_n, block)
    cum_q = blocked_cumsum(c_q, block)
    span_new = blocked_cumsum(c_span, block)
    return jnp.stack([n_in * decay + cum_n, q_in + cum_q, cum_n + cum_q,
                      spanq_in + span_new, span_new])
