"""Grouped-query attention: full (train/prefill) and paged-decode paths.

The decode path reads K/V through a *page-table indirection* into a KV
arena whose pages are allocated by ``core.jax_alloc`` — this is the
paper's allocator serving as the memory manager for inference state
(DESIGN.md §2.1).  The pure-jnp implementation here is the oracle; the
Pallas kernels in ``repro.kernels`` implement the same contracts with
VMEM tiling and are validated against these functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import param
from .rope import apply_rope, layer_rope

NEG_INF = -1e30


def init_attention(cfg, key):
    kq, kk, kv, ko, kb = jax.random.split(key, 5)
    d, h, k, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": param(kq, (d, h * dh), cfg.dtype),
        "wk": param(kk, (d, k * dh), cfg.dtype),
        "wv": param(kv, (d, k * dh), cfg.dtype),
        "wo": param(ko, (h * dh, d), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * dh,), cfg.dtype)
        p["bk"] = jnp.zeros((k * dh,), cfg.dtype)
        p["bv"] = jnp.zeros((k * dh,), cfg.dtype)
    return p


def _qkv(cfg, p, x, positions, window: int = 0):
    B, S, _ = x.shape
    h, k, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    kk = jnp.einsum("bsd,de->bse", x, p["wk"])
    v = jnp.einsum("bsd,de->bse", x, p["wv"])
    if cfg.qkv_bias:
        q, kk, v = q + p["bq"], kk + p["bk"], v + p["bv"]
    q = q.reshape(B, S, h, dh)
    kk = kk.reshape(B, S, k, dh)
    v = v.reshape(B, S, k, dh)
    if cfg.use_rope:
        rope = layer_rope(cfg, local=window > 0)
        q = apply_rope(q, positions, **rope)
        kk = apply_rope(kk, positions, **rope)
    return q, kk, v


def full_attention(cfg, p, x, positions, *, causal: bool = True,
                   window: int = 0):
    """Training / prefill attention.  Returns (out [B,S,D], (k, v))."""
    B, S, _ = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    q, k, v = _qkv(cfg, p, x, positions, window)
    qg = q.reshape(B, S, kvh, g, dh)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (dh ** -0.5)
    ii = positions[:, :, None] if positions.ndim == 2 else positions[None, :, None]
    jj = positions[:, None, :] if positions.ndim == 2 else positions[None, None, :]
    mask = jnp.ones((1, S, S), bool)
    if causal:
        mask = mask & (jj <= ii)
    if window:
        mask = mask & (jj > ii - window)
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    out = out.reshape(B, S, h * dh)
    return jnp.einsum("bse,ed->bsd", out, p["wo"]), (k, v)


def chunked_attention(cfg, p, x, positions, *, causal: bool = True,
                      window: int = 0, kv_chunk: int = 256):
    """Flash-style online-softmax attention over KV chunks.

    Never materializes the S×T score matrix: a ``lax.scan`` over KV
    chunks carries running (max, denominator, accumulator).  This is the
    XLA-level equivalent of FlashAttention and the pure-jnp oracle for
    ``kernels/flash_attention``.  ~2× the FLOPs of an ideal causal kernel
    (masked blocks are still computed — the Pallas kernel skips them).
    """
    B, S, _ = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    q, k, v = _qkv(cfg, p, x, positions, window)
    C = min(kv_chunk, S)
    while S % C:
        C -= 1
    nc = S // C
    qg = (q.reshape(B, S, kvh, g, dh) * (dh ** -0.5)).astype(jnp.float32)
    kc = k.reshape(B, nc, C, kvh, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, C, kvh, dh).transpose(1, 0, 2, 3, 4)
    qpos = positions if positions.ndim == 2 else positions[None]
    kpos = qpos.reshape(B, nc, C).transpose(1, 0, 2)

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, kp = inp
        s = jnp.einsum("bskgd,bckd->bskgc", qg, kb.astype(jnp.float32))
        valid = jnp.ones((B, S, C), bool)
        if causal:
            valid = valid & (kp[:, None, :] <= qpos[:, :, None])
        if window:
            valid = valid & (kp[:, None, :] > qpos[:, :, None] - window)
        s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
        m2 = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m2)
        e = jnp.exp(s - m2[..., None])
        l2 = l * corr + e.sum(axis=-1)
        acc2 = acc * corr[..., None] + jnp.einsum(
            "bskgc,bckd->bskgd", e, vb.astype(jnp.float32))
        return (m2, l2, acc2), None

    m0 = jnp.full((B, S, kvh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, kvh, g), jnp.float32)
    a0 = jnp.zeros((B, S, kvh, g, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kpos))
    out = (acc / jnp.maximum(l, 1e-20)[..., None]).astype(x.dtype)
    out = out.reshape(B, S, h * dh)
    return jnp.einsum("bse,ed->bsd", out, p["wo"]), (k, v)


def pallas_attention(cfg, p, x, positions, *, causal: bool = True,
                     window: int = 0):
    """Forward attention through the Pallas flash kernel (VMEM-tiled).

    On TPU this compiles to a Mosaic kernel; in the CPU dry-run the
    interpret-mode lowering (``runtime.pallas_interpret``) produces the
    same *traffic shape* (per-tile loads inside the grid loop instead of
    S×T score materialization), which is what the roofline memory term
    measures.  Forward-only: training wraps it in jax.checkpoint so the
    backward recomputes via the chunked path.
    """
    from ..kernels.flash_attention.kernel import flash_attention
    from ..runtime import pallas_interpret
    B, S, _ = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _qkv(cfg, p, x, positions, window)
    out = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=causal,
                          window=window, interpret=pallas_interpret())
    out = out.transpose(0, 2, 1, 3).reshape(B, S, h * dh)
    return jnp.einsum("bse,ed->bsd", out, p["wo"]), (k, v)


def attention_fwd(cfg, p, x, positions, *, causal: bool = True,
                  window: int = 0):
    """Dispatch on cfg.attn_impl: 'chunked' (default), 'naive', 'pallas'."""
    impl = getattr(cfg, "attn_impl", "chunked")
    if impl == "naive":
        return full_attention(cfg, p, x, positions, causal=causal,
                              window=window)
    if impl == "pallas":
        return pallas_attention(cfg, p, x, positions, causal=causal,
                                window=window)
    return chunked_attention(cfg, p, x, positions, causal=causal,
                             window=window)


def paged_decode_attention(cfg, p, x, pos, arena_k, arena_v, block_table,
                           kv_positions, *, window: int = 0):
    """One-token decode reading K/V through the page-table indirection.

    x:            [B, D]       current-token activations
    pos:          [B]          current position of each sequence
    arena_k/v:    [num_pages+1, page, K, Dh]   (last page = dump)
    block_table:  [B, P]       page ids (-1 → dump page)
    kv_positions: [B, P*page]  token position held by each slot (-1 invalid)

    Returns (out [B, D], (k_new, v_new)) — the caller is responsible for
    having scattered k_new/v_new into the arena *before* calling (see
    ``kvcache.append_kv``); kv_positions already reflects the new token.
    """
    B, D = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    page = arena_k.shape[1]
    P = block_table.shape[1]
    q = jnp.einsum("bd,de->be", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(B, h, dh)
    if cfg.use_rope:
        q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]

    dump = arena_k.shape[0] - 1
    bt = jnp.where(block_table < 0, dump, block_table)
    k = arena_k[bt].reshape(B, P * page, kvh, dh)     # gather via page table
    v = arena_v[bt].reshape(B, P * page, kvh, dh)
    qg = q.reshape(B, kvh, g, dh)
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k,
                        preferred_element_type=jnp.float32) * (dh ** -0.5)
    valid = (kv_positions >= 0) & (kv_positions <= pos[:, None])
    if window:
        valid = valid & (kv_positions > (pos[:, None] - window))
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bkgt,btkd->bkgd", probs, v).reshape(B, h * dh)
    return jnp.einsum("be,ed->bd", out, p["wo"])


def decode_kv(cfg, p, x, pos):
    """Current token's k/v (for the caller to scatter into the arena)."""
    kk = jnp.einsum("bd,de->be", x, p["wk"])
    v = jnp.einsum("bd,de->be", x, p["wv"])
    if cfg.qkv_bias:
        kk, v = kk + p["bk"], v + p["bv"]
    kvh, dh = cfg.num_kv_heads, cfg.head_dim
    kk = kk.reshape(x.shape[0], kvh, dh)
    v = v.reshape(x.shape[0], kvh, dh)
    if cfg.use_rope:
        kk = apply_rope(kk[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    return kk, v
