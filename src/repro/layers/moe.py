"""Mixture-of-experts FFN with top-k routing (granite-MoE, Moonlight).

Dispatch is argsort-based with a static per-expert capacity (GShard-style
token dropping) — ragged grouping is expressed as sort + segment
positions so every shape stays static for XLA.  Experts are sharded over
the ``model`` mesh axis when divisible (expert parallelism; the
resharding materializes as all-to-alls in the lowered HLO), otherwise
over ``d_ff`` (tensor parallelism) — see ``distributed.sharding``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import param


def init_moe(cfg, key):
    """The router spans every routed expert; ``num_experts`` (the experts
    held here) each get their own matrices, scaled by their fan-in."""
    kr, k1, k2, k3 = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    ep = e + cfg.expert_pad          # zero-padded so E divides the TP axis
    p = {
        "router": param(kr, (d, cfg.routed_experts), jnp.float32),
        "wi": param(k1, (ep, d, f), cfg.dtype, scale=d ** -0.5),
        "wo": param(k3, (ep, f, d), cfg.dtype, scale=f ** -0.5),
    }
    if cfg.mlp == "swiglu":
        p["wg"] = param(k2, (ep, d, f), cfg.dtype, scale=d ** -0.5)
    return p


def apply_moe(cfg, p, x, *, capacity_factor: float = 1.25,
              constrain=lambda a: a):
    """Dispatch router: 'local' (per-batch-row, shard-friendly) or the
    original 'global' argsort dispatch.  Both compute a whole expert
    set; a held share of the routed experts is served by
    ``serving.tp_layers.moe_decode_tp``."""
    if cfg.routed_experts != cfg.num_experts:
        raise NotImplementedError(
            f"{cfg.name}: the full-sequence MoE path computes all routed "
            f"experts, and this configuration holds {cfg.num_experts} of "
            f"{cfg.routed_experts}")
    if getattr(cfg, "moe_dispatch", "global") == "local":
        return apply_moe_local(cfg, p, x, capacity_factor=capacity_factor,
                               constrain=constrain)
    return apply_moe_global(cfg, p, x, capacity_factor=capacity_factor)


def apply_moe_local(cfg, p, x, *, capacity_factor: float = 1.25,
                    constrain=lambda a: a):
    """Per-batch-row dispatch: every token's (sort, scatter, gather) stays
    within its own batch row, so with batch sharded over the data axes the
    dispatch generates **zero cross-data-shard collectives** — only the
    expert contraction communicates (over the model/EP axis).

    §Perf B3: the global-argsort dispatch below sorts all B·S·K
    assignments jointly, which XLA partitions with all-to-alls and
    all-reduces across data; measured 92 s collective term on
    granite-moe train_4k.  Capacity here is per row (1.25·S·K/E), so
    drop behaviour differs slightly from the global router at row-level
    load imbalance — same expectation, tested for parity at high
    capacity_factor.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    N = S * K

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, K)                  # [B, S, K]
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.zeros((E,)).at[expert.reshape(-1)].add(1.0) / (B * N)
    aux = E * jnp.sum(me * ce)

    cap = int(max(1, round(capacity_factor * N / E)))
    flat_e = expert.reshape(B, N)                           # [B, S*K]
    order = jnp.argsort(flat_e, axis=-1, stable=True)
    sorted_e = jnp.take_along_axis(flat_e, order, axis=-1)
    start = jax.vmap(lambda row: jnp.searchsorted(
        row, jnp.arange(E, dtype=jnp.int32)))(sorted_e)     # [B, E]
    pos = jnp.arange(N, dtype=jnp.int32)[None] - \
        jnp.take_along_axis(start, sorted_e, axis=-1)
    b_ix = jnp.arange(B, dtype=jnp.int32)[:, None]
    ranks = jnp.zeros((B, N), jnp.int32).at[b_ix, order].set(pos)
    keep = ranks < cap

    slot = jnp.where(keep, flat_e * cap + ranks, E * cap)   # per-row dump
    tok = jnp.repeat(jnp.arange(S, dtype=jnp.int32)[None], B, 0)
    tok = jnp.repeat(tok, K, axis=-1).reshape(B, N)
    buf = jnp.zeros((B, E * cap + 1, D), x.dtype)
    buf = buf.at[b_ix, slot].set(
        jnp.take_along_axis(x, tok[..., None], axis=1))
    # keep the dispatch buffer batch-sharded: without the constraint XLA
    # replicates its batch dim and all-reduces it across data (§Perf B3)
    buf = constrain(buf)
    buf = buf[:, :-1].reshape(B, E, cap, D)

    h = jnp.einsum("becd,edf->becf", buf, p["wi"][:E])
    if cfg.mlp == "swiglu":
        g = jnp.einsum("becd,edf->becf", buf, p["wg"][:E])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    elif cfg.mlp == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    out_e = constrain(jnp.einsum("becf,efd->becd", h, p["wo"][:E]))
    out_flat = out_e.reshape(B, E * cap, D)

    gathered = jnp.take_along_axis(
        out_flat, jnp.clip(slot, 0, E * cap - 1)[..., None], axis=1)
    gathered = jnp.where(keep[..., None], gathered, 0).astype(jnp.float32)
    y = jnp.zeros((B, S, D), jnp.float32).at[b_ix, tok].add(
        gathered * gate.reshape(B, N)[..., None])
    return y.astype(x.dtype), aux


def apply_moe_global(cfg, p, x, *, capacity_factor: float = 1.25):
    """x: [B, S, D] → [B, S, D] plus aux load-balancing loss."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.top_k
    N = B * S
    xf = x.reshape(N, D)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, K)                    # [N, K]
    gate = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch): E * Σ_e f_e · P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,)).at[expert.reshape(-1)].add(1.0) / (N * K)
    aux = E * jnp.sum(me * ce)

    cap = int(max(1, round(capacity_factor * N * K / E)))
    flat_e = expert.reshape(-1)                               # [N*K]
    # position of each (token, k) within its expert queue
    order = jnp.argsort(flat_e, stable=True)
    ranks = jnp.zeros((N * K,), jnp.int32)
    seq = jnp.arange(N * K, dtype=jnp.int32)
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=jnp.int32))
    pos_in_e = seq - start[sorted_e]
    ranks = ranks.at[order].set(pos_in_e)
    keep = ranks < cap                                        # dropped beyond cap

    # scatter tokens into the [E, cap, D] buffer
    slot = jnp.where(keep, flat_e * cap + ranks, E * cap)     # dump slot
    buf = jnp.zeros((E * cap + 1, D), x.dtype)
    tok_idx = jnp.repeat(jnp.arange(N, dtype=jnp.int32), K)
    buf = buf.at[slot].set(xf[tok_idx])
    buf = buf[:-1].reshape(E, cap, D)

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"][:E])
    if cfg.mlp == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", buf, p["wg"][:E])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * h
    elif cfg.mlp == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    out_e = jnp.einsum("ecf,efd->ecd", h, p["wo"][:E]).reshape(E * cap, D)

    # gather back and combine with gates
    gathered = jnp.where(keep[:, None], out_e[jnp.clip(slot, 0, E * cap - 1)],
                         0).astype(jnp.float32)
    y = jnp.zeros((N, D), jnp.float32).at[tok_idx].add(
        gathered * gate.reshape(-1)[:, None])
    return y.astype(x.dtype).reshape(B, S, D), aux
