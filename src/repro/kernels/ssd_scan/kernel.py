"""Mamba-2 SSD chunked-scan Pallas TPU kernel (forward).

One (batch, head) pair per grid row; the chunk dimension runs
sequentially per core carrying the [P, N] inter-chunk SSM state in VMEM
scratch — the same carry-in-scratch schedule as the flash kernel.  Per
chunk the kernel computes the quadratic dual form on the MXU:

  y_intra = (C Bᵀ ⊙ L) · (x·dt)          L = causal decay mask
  y_inter = (C · h_in) ⊙ exp(cumsum log a)
  h_out   = h_in · exp(Σ log a) + Σ decay_out · B ⊗ (x·dt)

Inputs are pre-discretized (x·dt and log-decay per step), matching
``layers.ssd.ssd_chunked`` — which is the pure-jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(xdt_ref, loga_ref, b_ref, c_ref, y_ref, h_scr, *,
                nchunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    xdt = xdt_ref[0].astype(jnp.float32)        # [Q, P]
    loga = loga_ref[0].astype(jnp.float32)      # [1, Q]
    B = b_ref[0].astype(jnp.float32)            # [Q, N]
    C = c_ref[0].astype(jnp.float32)            # [Q, N]
    Q = xdt.shape[0]

    # inclusive prefix sums of the log-decay as one matmul with the
    # causal ones mask (Mosaic has no cumsum), in row and column form
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = ii >= jj
    tri = causal.astype(jnp.float32)
    exact = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot_general(tri, loga, (((1,), (1,)), ((), ())),
                                  precision=exact,
                                  preferred_element_type=jnp.float32)  # [Q, 1]
    cum_row = jax.lax.dot_general(loga, tri, (((1,), (1,)), ((), ())),
                                  precision=exact,
                                  preferred_element_type=jnp.float32)  # [1, Q]
    total = jnp.sum(loga)
    # intra-chunk quadratic form
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, Q]
    L = jnp.where(causal, jnp.exp(cum_col - cum_row), 0.0)
    y_intra = jax.lax.dot_general(G * L, xdt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk contribution from the carried state  h: [P, N]
    h = h_scr[...]
    y_inter = jax.lax.dot_general(C, h, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum_col)
    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update
    xb = xdt * jnp.exp(total - cum_col)                     # [Q, P]
    dh = jax.lax.dot_general(xb, B, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [P, N]
    h_scr[...] = h * jnp.exp(total) + dh


def ssd_scan(xdt, loga, B, C, *, interpret: bool = False):
    """xdt: [Bz, H, S, P]; loga: [Bz, H, S]; B/C: [Bz, S, N] (shared
    across heads).  Chunk = 128 steps.  Returns y [Bz, H, S, P] fp32."""
    Bz, H, S, P = xdt.shape
    N = B.shape[-1]
    Q = min(128, S)
    assert S % Q == 0
    nc = S // Q

    xf = xdt.reshape(Bz * H, S, P)
    # a unit middle axis makes the (1, Q) block trailing dims tiling-legal
    # (1 = the whole axis, Q a multiple of 128 or the whole sequence)
    lf = loga.reshape(Bz * H, 1, S)
    # broadcast B/C across heads to keep the index maps affine
    bf = jnp.repeat(B, H, axis=0).reshape(Bz * H, S, N)
    cf = jnp.repeat(C, H, axis=0).reshape(Bz * H, S, N)

    grid = (Bz * H, nc)
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, nchunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda bh, c: (bh, 0, c)),
            pl.BlockSpec((1, Q, N), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, c: (bh, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bz * H, S, P), jnp.float32),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, lf, bf, cf)
    return out.reshape(Bz, H, S, P)
