"""Tensor-parallel decode layers (run inside ``jax.shard_map``).

Decode reads K/V through the paged arena, so the natural distribution is

  * batch over the ``data`` (+``pod``) axes — every sequence, its block
    table and its pages live on exactly one data shard (page ids are
    shard-local: one allocator instance per data shard, mirroring the
    paper's multi-heap/process model);
  * within a data shard, the ``model`` axis shards *page slots*: each of
    the tp chips holds page_size/tp slots of every page.  Attention
    computes per-shard partial softmax statistics and merges them with a
    pmax + psum — distributed FlashDecoding.  This works for any number
    of KV heads (GQA kv=1 included), which head-sharding cannot do;
  * weights are row/column-parallel over ``model`` (Megatron-style), so
    each layer costs a handful of tiny [B, ·] psums.

All functions here take *local* shards; ``axis`` is the model axis name.
They are exercised at tp=1 by the CPU tests and at tp=16 by the dry-run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..layers.rope import apply_rope, layer_rope
from ..runtime import axis_size

NEG_INF = -1e30


def _tp(axis):
    return axis_size(axis)


def _idx(axis):
    return lax.axis_index(axis)


def _xslice(x, axis):
    """Local slice of a model-replicated activation along its last dim."""
    tp = _tp(axis)
    d = x.shape[-1] // tp
    return lax.dynamic_slice_in_dim(x, _idx(axis) * d, d, x.ndim - 1)


# ---------------------------------------------------------------------------
# embedding / logits (vocab-parallel)
# ---------------------------------------------------------------------------
def embed_tp(table_loc, tokens, axis, sharded: bool = True):
    """Vocab-sharded embedding gather + psum (plain gather if replicated)."""
    if not sharded:
        return table_loc[tokens]
    v_loc = table_loc.shape[0]
    off = _idx(axis) * v_loc
    local = tokens - off
    ok = (local >= 0) & (local < v_loc)
    rows = table_loc[jnp.clip(local, 0, v_loc - 1)]
    return lax.psum(jnp.where(ok[:, None], rows, 0), axis)


def logits_tp(table_loc, x, axis):
    """Vocab-sharded logits [B, V_loc] (caller merges/samples)."""
    return jnp.einsum("bd,vd->bv", x, table_loc,
                      preferred_element_type=jnp.float32)


def greedy_sample_tp(logits_loc, axis, sharded: bool = True):
    """Greedy token from vocab-sharded logits via local argmax + gather."""
    if not sharded:
        return jnp.argmax(logits_loc, axis=1).astype(jnp.int32)
    v_loc = logits_loc.shape[1]
    loc_max = jnp.max(logits_loc, axis=1)
    loc_arg = jnp.argmax(logits_loc, axis=1) + _idx(axis) * v_loc
    allm = lax.all_gather(loc_max, axis)              # [tp, B]
    alla = lax.all_gather(loc_arg, axis)
    winner = jnp.argmax(allm, axis=0)                 # [B]
    return jnp.take_along_axis(alla, winner[None], axis=0)[0].astype(jnp.int32)


# ---------------------------------------------------------------------------
# attention decode: slot-sharded paged KV + distributed softmax merge
# ---------------------------------------------------------------------------
def dp_linear_index(dp_axes) -> jax.Array:
    """Flattened index over (possibly several) data axes."""
    out = jnp.int32(0)
    for a in dp_axes:
        out = out * axis_size(a) + lax.axis_index(a)
    return out


def table_slot(pos, P, page_loc, axis, seq_dp_axes=(), ring=False):
    """Where each lane's token at ``pos`` lives in a page table of local
    width ``P``: (this shard holds it, its local table column, its local
    slot in the page).  A ring table wraps: the token's page is column
    ``pos // page`` modulo the table's global width, so the page that
    leaves a window layer's window is the one written next."""
    page = page_loc * _tp(axis)
    slot = pos % page
    mine = (slot // page_loc) == _idx(axis)
    gpage = pos // page                       # global page index of the token
    if ring:
        n_dp = 1
        for a in seq_dp_axes:
            n_dp *= axis_size(a)
        gpage = gpage % (P * n_dp)
    if seq_dp_axes:
        mine = mine & ((gpage // P) == dp_linear_index(seq_dp_axes))
        lpage = gpage % P
    else:
        lpage = gpage
    return mine, lpage, jnp.where(mine, slot % page_loc, 0)


def attn_decode_tp(cfg, p, x, pos, arena_k, arena_v, block_table, kv_pos,
                   *, window: int = 0, axis: str = "model",
                   seq_dp_axes: tuple = (), scales=None, unit=None):
    """One-token paged attention.  A window layer (``window`` > 0) reads
    a ring table (``table_slot``) and plain RoPE; a full layer its
    position-indexed table and, where the configuration scales it, YaRN.

    x:           [B, D] replicated over ``axis``
    arena_k/v:   [U, pages_loc, page_loc, K, dh] when ``unit`` (an i32
                 scalar) is given: the arena of every pattern unit,
                 carried through the layer loop and indexed in place at
                 ``unit``, both where the new token is written and where
                 pages are gathered; [pages_loc, page_loc, K, dh] (one
                 layer's arena, a tail layer's) when it is not.  Local
                 slot shard, +1 dump page.
    block_table: [B, P_loc] shard-local page ids (-1 unused)
    kv_pos:      [B, P_loc, page_loc] position per local slot (-1 invalid)
    scales:      int8 K/V's (ks, vs), each the arena's shape without its
                 last axis: [U, pages_loc, page_loc, K] or
                 [pages_loc, page_loc, K]

    When ``seq_dp_axes`` is non-empty, one sequence's *pages* are sharded
    across those data axes (sequence parallelism for batch < dp, e.g. the
    long_500k shape) and the softmax merge spans (dp_axes + model).

    Returns (y [B, D], arena_k', arena_v', kv_pos', scales'), the arenas
    and scales in the shapes they came in.
    """
    B, D = x.shape
    h, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kvh
    page_loc = arena_k.shape[-3]
    P = block_table.shape[1]
    dump = arena_k.shape[-4] - 1
    at = () if unit is None else (unit,)      # leading index into the arena
    merge_axes = tuple(seq_dp_axes) + (axis,)

    # fused row-parallel qkv: one psum
    xs = _xslice(x, axis)
    qp = jnp.einsum("bd,de->be", xs, p["wq"])
    kp = jnp.einsum("bd,de->be", xs, p["wk"])
    vp = jnp.einsum("bd,de->be", xs, p["wv"])
    qkv = lax.psum(jnp.concatenate([qp, kp, vp], axis=-1), axis)
    q, k_new, v_new = jnp.split(qkv, [h * dh, h * dh + kvh * dh], axis=-1)
    if cfg.qkv_bias:
        q, k_new, v_new = q + p["bq"], k_new + p["bk"], v_new + p["bv"]
    q = q.reshape(B, h, dh)
    k_new = k_new.reshape(B, kvh, dh)
    v_new = v_new.reshape(B, kvh, dh)
    if cfg.use_rope:
        rope = layer_rope(cfg, local=window > 0)
        q = apply_rope(q[:, None], pos[:, None], **rope)[:, 0]
        k_new = apply_rope(k_new[:, None], pos[:, None], **rope)[:, 0]

    # scatter the new token's k/v into the (dp, slot) shard that owns it
    mine, lpage, lslot = table_slot(pos, P, page_loc, axis, seq_dp_axes,
                                    ring=window > 0)
    pid = jnp.take_along_axis(block_table, lpage[:, None], axis=1)[:, 0]
    pid_w = jnp.where(mine & (pid >= 0), pid, dump)
    b_ix = jnp.arange(B)
    with jax.named_scope("kv_write"):
        if scales is not None:
            # int8 KV (KIVI-style per-slot-per-head scales): quantize the new
            # token's k/v, store int8 + fp32 scale; dequantize on gather
            ks, vs = scales
            k_s = (jnp.max(jnp.abs(k_new.astype(jnp.float32)), -1)
                   / 127.0 + 1e-9)
            v_s = (jnp.max(jnp.abs(v_new.astype(jnp.float32)), -1)
                   / 127.0 + 1e-9)
            kq = jnp.clip(jnp.round(k_new.astype(jnp.float32)
                                    / k_s[..., None]),
                          -127, 127).astype(jnp.int8)
            vq = jnp.clip(jnp.round(v_new.astype(jnp.float32)
                                    / v_s[..., None]),
                          -127, 127).astype(jnp.int8)
            arena_k = arena_k.at[at + (pid_w, lslot)].set(kq)
            arena_v = arena_v.at[at + (pid_w, lslot)].set(vq)
            ks = ks.at[at + (pid_w, lslot)].set(k_s)
            vs = vs.at[at + (pid_w, lslot)].set(v_s)
        else:
            arena_k = arena_k.at[at + (pid_w, lslot)].set(
                k_new.astype(arena_k.dtype))
            arena_v = arena_v.at[at + (pid_w, lslot)].set(
                v_new.astype(arena_v.dtype))
        kv_pos = kv_pos.at[b_ix, lpage, lslot].set(
            jnp.where(mine & (pid >= 0), pos, kv_pos[b_ix, lpage, lslot]))

    # local paged gather
    with jax.named_scope("kv_gather"):
        bt = jnp.where(block_table < 0, dump, block_table)
        kloc = arena_k[at + (bt,)].reshape(B, P * page_loc, kvh, dh)
        vloc = arena_v[at + (bt,)].reshape(B, P * page_loc, kvh, dh)
        if scales is not None:
            ksl = ks[at + (bt,)].reshape(B, P * page_loc, kvh)[..., None]
            vsl = vs[at + (bt,)].reshape(B, P * page_loc, kvh)[..., None]
            kloc = (kloc.astype(jnp.float32) * ksl).astype(x.dtype)
            vloc = (vloc.astype(jnp.float32) * vsl).astype(x.dtype)
    # partial softmax
    qg = q.reshape(B, kvh, g, dh)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, kloc,
                   preferred_element_type=jnp.float32) * (dh ** -0.5)
    kvp = kv_pos.reshape(B, P * page_loc)
    valid = (kvp >= 0) & (kvp <= pos[:, None])
    if window:
        valid = valid & (kvp > (pos[:, None] - window))
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                  # [B,K,G]
    M = lax.pmax(m, merge_axes)
    e = jnp.exp(s - M[..., None])
    l = jnp.sum(e, axis=-1)
    acc = jnp.einsum("bkgt,btkd->bkgd", e.astype(vloc.dtype), vloc)
    # merge partial (l, acc) across the KV shards in one psum
    merged = lax.psum(
        jnp.concatenate([acc.astype(jnp.float32),
                         l[..., None]], axis=-1), merge_axes)
    out = merged[..., :dh] / jnp.maximum(merged[..., dh:], 1e-20)
    out = out.reshape(B, h * dh).astype(x.dtype)

    # row-parallel output projection
    os = _xslice(out, axis)
    wo_loc = p["wo"]
    y = lax.psum(jnp.einsum("be,ed->bd", os, wo_loc), axis)
    new_scales = (ks, vs) if scales is not None else None
    return y, arena_k, arena_v, kv_pos, new_scales


# ---------------------------------------------------------------------------
# ffn
# ---------------------------------------------------------------------------
def mlp_decode_tp(cfg, p, x, axis):
    h = jnp.einsum("bd,df->bf", x, p["wi"])
    if cfg.mlp == "swiglu":
        gg = jnp.einsum("bd,df->bf", x, p["wg"])
        h = jax.nn.silu(gg.astype(jnp.float32)).astype(x.dtype) * h
    elif cfg.mlp == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return lax.psum(jnp.einsum("bf,fd->bd", h, p["wo"]), axis)


def moe_decode_tp(cfg, p, x, axis):
    """Expert-parallel decode over the experts held here: the router
    (float32 at HIGHEST precision, at its published width) scores every
    routed expert; a token's top k are the experts ranked below k by
    logit (ties to the lower id, as ``lax.top_k`` breaks them), weighted
    by softmax over those k (equal to softmax over all, then top-k,
    then renormalised); each held expert (``cfg.expert_first`` onwards,
    split over ``axis``) runs densely over the (small) token batch, and
    gates mask the combine, so an expert held elsewhere adds nothing;
    one psum merges shards."""
    e_loc = p["wi"].shape[0]
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            logits = jnp.einsum("bd,de->be", x.astype(jnp.float32),
                                p["router"], precision=lax.Precision.HIGHEST)
            E = logits.shape[-1]
            ids = jnp.arange(E)
            above = ((logits[:, None, :] > logits[:, :, None])
                     | ((logits[:, None, :] == logits[:, :, None])
                        & (ids[None, :] < ids[:, None])))
            chosen = above.sum(-1) < cfg.top_k                   # [B, E]
            w = jnp.where(chosen, jnp.exp(
                logits - logits.max(-1, keepdims=True)), 0.0)
            gate = w / w.sum(-1, keepdims=True)
            # this shard's experts (padding past the held ones gates 0)
            first = cfg.expert_first + _idx(axis) * e_loc
            gate = jnp.pad(gate, ((0, 0), (0, max(
                0, cfg.expert_first + e_loc * _tp(axis) - E))))
            gl = jnp.where(
                first + jnp.arange(e_loc) < cfg.expert_first + cfg.num_experts,
                lax.dynamic_slice_in_dim(gate, first, e_loc, 1), 0.0)
        with jax.named_scope("experts"):
            h = jnp.einsum("bd,edf->ebf", x, p["wi"])
            if cfg.mlp == "swiglu":
                gg = jnp.einsum("bd,edf->ebf", x, p["wg"])
                h = jax.nn.silu(gg.astype(jnp.float32)).astype(x.dtype) * h
            else:
                h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
            y = jnp.einsum("ebf,efd->ebd", h, p["wo"])
            y = jnp.einsum("ebd,be->bd", y.astype(jnp.float32), gl)
    return lax.psum(y, axis).astype(x.dtype)


# ---------------------------------------------------------------------------
# recurrent mixers
# ---------------------------------------------------------------------------
def mamba2_decode_tp(cfg, p, x, state, axis):
    """Head-sharded single-token SSD update (B/C replicated)."""
    from ..layers import ssd as ssd_lib
    B, D = x.shape
    N = cfg.ssm_state
    P = cfg.ssm_head_dim
    z = jnp.einsum("bd,de->be", x, p["in_z"])            # [B, Di_loc]
    xs = jnp.einsum("bd,de->be", x, p["in_x"]).astype(jnp.float32)
    bc = jnp.einsum("bd,de->be", x, p["in_bc"]).astype(jnp.float32)
    dt = jnp.einsum("bd,de->be", x, p["in_dt"])          # [B, H_loc]
    hist_x = jnp.concatenate([state["conv_x"], xs[:, None, :]], axis=1)
    hist_bc = jnp.concatenate([state["conv_bc"], bc[:, None, :]], axis=1)
    cx = jnp.einsum("bwc,wc->bc", hist_x, p["conv_x_w"].astype(jnp.float32))
    cx = jax.nn.silu(cx + p["conv_x_b"].astype(jnp.float32))
    cbc = jnp.einsum("bwc,wc->bc", hist_bc, p["conv_bc_w"].astype(jnp.float32))
    cbc = jax.nn.silu(cbc + p["conv_bc_b"].astype(jnp.float32))
    Bm, Cm = cbc[:, :N], cbc[:, N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))
    h_loc = cx.shape[1] // P
    xh = cx.reshape(B, h_loc, P)
    hidden = (state["h"] * a[:, :, None, None]
              + jnp.einsum("bn,bhp,bh->bhpn", Bm, xh, dt))
    y = jnp.einsum("bn,bhpn->bhp", Cm, hidden) + p["D"][None, :, None] * xh
    y = y.reshape(B, -1) * jax.nn.silu(z.astype(jnp.float32))
    # distributed gated RMSNorm: global mean of squares over d_inner
    di = y.shape[1] * _tp(axis)
    ssq = lax.psum(jnp.sum(y * y, axis=-1, keepdims=True), axis) / di
    y = y * lax.rsqrt(ssq + 1e-6) * p["norm_w"]
    out = lax.psum(jnp.einsum("be,ed->bd", y.astype(x.dtype), p["out_proj"]),
                   axis)
    return out, {"h": hidden, "conv_x": hist_x[:, 1:], "conv_bc": hist_bc[:, 1:]}


def rglru_decode_tp(cfg, p, x, state, axis):
    """Width-sharded single-token RG-LRU update."""
    _C = 8.0
    xr = jnp.einsum("bd,dw->bw", x, p["in_x"]).astype(jnp.float32)  # [B,W_loc]
    xg = jnp.einsum("bd,dw->bw", x, p["in_g"])
    hist = jnp.concatenate([state["conv"], xr[:, None, :]], axis=1)
    conv = jnp.einsum("bwc,wc->bc", hist, p["conv_w"].astype(jnp.float32))
    conv = conv + p["conv_b"].astype(jnp.float32)
    # row-parallel gate projections: psum yields the full pre-activation,
    # then each shard keeps its local width slice
    ga = jnp.einsum("bw,wv->bv", conv.astype(x.dtype), p["wa"])
    gi = jnp.einsum("bw,wv->bv", conv.astype(x.dtype), p["wx"])
    gfull = lax.psum(jnp.concatenate([ga, gi], axis=-1), axis)
    W = gfull.shape[-1] // 2
    w_loc = p["in_x"].shape[1]
    off = _idx(axis) * w_loc
    r = jax.nn.sigmoid(lax.dynamic_slice_in_dim(
        gfull[:, :W], off, w_loc, 1).astype(jnp.float32))
    i = jax.nn.sigmoid(lax.dynamic_slice_in_dim(
        gfull[:, W:], off, w_loc, 1).astype(jnp.float32))
    a = jnp.exp(-_C * r * jax.nn.softplus(p["lam"]))
    b = jnp.sqrt(jnp.clip(1.0 - a * a, 1e-12)) * (i * conv)
    h = a * state["h"] + b
    y = h * jax.nn.gelu(xg.astype(jnp.float32))
    out = lax.psum(jnp.einsum("bw,wd->bd", y.astype(x.dtype), p["out"]), axis)
    return out, {"h": h, "conv": hist[:, 1:]}
