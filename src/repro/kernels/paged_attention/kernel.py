"""Paged decode-attention Pallas TPU kernel.

One query token per sequence attends over K/V pages resolved through a
block table — the compute face of the Ralloc page allocator: block-table
entries are the *position-independent offsets* the allocator hands out
(DESIGN.md §2.1).

TPU schedule: grid = (batch, pages); the page dimension runs
sequentially per core, carrying each KV head's online-softmax state in
VMEM scratch.  Each step reads one page of K and V for every KV head —
each page crosses HBM once — and attends every query-head group to its
head.  The block table and sequence lengths ride in scalar-prefetch
SMEM so the page→HBM address indirection happens in the BlockSpec index
map (pages stream HBM→VMEM double-buffered by the Pallas pipeline).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page: int, npages: int,
                  n_kv: int, scale: float, window: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    pid = bt_ref[b, p]
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)[0]
    valid = (pos < length) & (pid >= 0)
    if window:
        valid = valid & (pos > length - 1 - window)

    @pl.when(jnp.any(valid))
    def _attend():
        # a static head index keeps each load's offset provable to the
        # TPU compiler
        for h in range(n_kv):
            q = q_ref[0, h].astype(jnp.float32) * scale      # [G, dh]
            k = k_ref[0, :, h].astype(jnp.float32)           # [page, dh]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(valid[None, :], s, NEG_INF)        # [G, page]
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1))
            corr = jnp.exp(m_prev - m_new)
            e = jnp.exp(s - m_new[:, None])
            l_scr[h] = l_scr[h] * corr + e.sum(axis=1)
            v = v_ref[0, :, h].astype(jnp.float32)           # [page, dh]
            acc_scr[h] = acc_scr[h] * corr[:, None] + \
                jax.lax.dot_general(e, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(p == npages - 1)
    def _final():
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0] = (acc_scr[...] / l[..., None]).astype(o_ref.dtype)


def paged_attention(q, arena_k, arena_v, block_table, lengths, *,
                    window: int = 0, interpret: bool = False):
    """q: [B, H, dh]; arena_k/v: [pages, page, K, dh];
    block_table: [B, P] page ids (-1 unused); lengths: [B] tokens held.

    Pages are filled contiguously (engine contract); returns [B, H, dh].
    """
    B, H, dh = q.shape
    npages_tot, page, K, _ = arena_k.shape
    P = block_table.shape[1]
    g = H // K
    scale = dh ** -0.5
    qg = q.reshape(B, K, g, dh)

    grid = (B, P)
    kernel = functools.partial(_paged_kernel, page=page, npages=P,
                               n_kv=K, scale=scale, window=window)
    # K/V blocks hold every KV head of one page: (K, dh) is the whole
    # trailing extent, which TPU tiling accepts for any K
    kv_spec = pl.BlockSpec((1, page, K, dh),
                           lambda b, p, bt, ln:
                           (jnp.maximum(bt[b, p], 0), 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, K, g, dh),
                             lambda b, p, bt, ln: (b, 0, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec((1, K, g, dh),
                                   lambda b, p, bt, ln: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((K, g), jnp.float32),
                pltpu.VMEM((K, g), jnp.float32),
                pltpu.VMEM((K, g, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, g, dh), q.dtype),
        interpret=interpret,
    )(block_table, lengths, qg, arena_k, arena_v)
    return out.reshape(B, H, dh)
