"""serve_step (decode): shard_map assembly over (pod, data, model).

One decode step = embed → scan over pattern units (each unit applies its
mixers/ffns via ``tp_layers``) → tail layers → final norm →
vocab-parallel logits → greedy sample.  The unit-stacked decode state
is the scan's carry, not a scanned input and output: attention writes
the new token's K/V into the stacked arena [U, pages, page, K, dh] at
``[unit, page, slot]`` and gathers ``[unit, block_table]`` in place, and
a recurrent mixer reads its unit's slice of its small per-batch state
and writes it back, so neither a layer nor the step copies the arena.
Full-attention layers index their arena through ``block_table`` by
page number; window layers (``local_attn``) through ``ring_table``, a
ring of ``cfg.ring_pages`` slots a lane whose arena is its own, so the
page that leaves the window is the one overwritten next.
Named scopes (``attention`` and, for window layers,
``window_attention``, with ``kv_write`` and ``kv_gather`` inside them,
``ffn`` with ``moe`` holding ``router`` and ``experts``, ``logits``)
label the compiled ops in a profile.  Batch
and KV pages are sharded over the data axes (shard-local page ids — one
arena per data shard); ``model`` carries Megatron-style TP plus
slot-sharded paged attention.

The decode state is a pytree:

  {"pos": i32[B], "block_table": i32[B, P], "kv_pos": i32[B, P, page],
   "units": {"l<i>": mixer-state stacked over units}, "tail": {...}}

and, with window layers, "ring_table": i32[B, R] and "ring_kv_pos":
i32[B, R, page].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..layers.common import apply_norm
from ..models.config import ModelConfig
from ..runtime import shard_map
from . import tp_layers as tpl

MODEL_AXIS = "model"
# each attention kind's page table and slot positions in the decode state
TABLES = {"attn": ("block_table", "kv_pos"),
          "local_attn": ("ring_table", "ring_kv_pos")}


def data_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------
def serve_param_specs(cfg: ModelConfig, params_shape, tp: int = 16) -> dict:
    """PartitionSpecs for the serving weight layout (model-axis TP only).

    Vocab tables whose row count does not divide the TP axis are
    replicated (internvl2: 92553, granite-moe: 49155, hubert: 504).
    """
    M = MODEL_AXIS
    vocab_ok = cfg.vocab_size % tp == 0

    def spec_for(path: str, ndim: int, lead: int):
        pre = (None,) * lead

        def p(*s):
            return P(*(pre + s + (None,) * (ndim - lead - len(s))))

        last = path.split("/")[-1]
        if "attn" in path:
            if last in ("wq", "wk", "wv", "wo"):
                return p(M, None)
            return p()                        # biases replicated
        if "ffn" in path:
            if last == "router":
                return p()
            if last in ("wi", "wg"):
                return p(M) if ndim - lead == 3 else p(None, M)
            if last == "wo":
                return p(M) if ndim - lead == 3 else p(M, None)
        if "ssd" in path:
            if last in ("in_z", "in_x", "in_dt", "conv_x_w"):
                return p(None, M)
            if last in ("conv_x_b", "A_log", "dt_bias", "D", "norm_w"):
                return p(M)
            if last == "out_proj":
                return p(M, None)
            return p()                        # in_bc / conv_bc_* replicated
        if "rglru" in path:
            if last in ("in_x", "in_g", "conv_w"):
                return p(None, M)
            if last in ("conv_b", "lam"):
                return p(M)
            if last in ("wa", "wx", "out"):
                return p(M, None)
            return p()
        if last in ("embed", "unembed"):
            return P(M, None) if vocab_ok else P(None, None)
        return p()                            # norms etc. replicated

    def walk(tree, path="", lead=0):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                l2 = lead + 1 if k == "units" and path == "" else lead
                out[k] = walk(v, f"{path}/{k}", l2)
            return out
        return spec_for(path, len(tree.shape), lead)

    return walk(params_shape)


def mixer_state_specs(cfg: ModelConfig, mesh, stacked: bool,
                      batch_sharded: bool):
    """Specs for one pattern position's state (optionally unit-stacked).

    With ``batch_sharded`` the batch dim is split over the data axes and
    each data shard keeps its own sequences' pages.  Otherwise (batch <
    dp, e.g. long_500k) the *pages* are split over the data axes —
    sequence parallelism — and recurrent states are replicated over dp.
    """
    dp = data_axes(mesh)
    pre = (None,) if stacked else ()
    M = MODEL_AXIS
    bdp = dp if batch_sharded else None

    def mk(*s):
        return P(*(pre + s))

    out = {}
    for i, (mixer, _) in enumerate(cfg.pattern):
        key = f"l{i}"
        if mixer in ("attn", "local_attn"):
            out[key] = {"k": mk(dp, M, None, None),
                        "v": mk(dp, M, None, None)}
            if cfg.kv_dtype == "int8":
                out[key]["ks"] = mk(dp, M, None)
                out[key]["vs"] = mk(dp, M, None)
        elif mixer == "mamba2":
            out[key] = {"h": mk(bdp, M, None, None),
                        "conv_x": mk(bdp, None, M),
                        "conv_bc": mk(bdp, None, None)}
        elif mixer == "rglru":
            out[key] = {"h": mk(bdp, M), "conv": mk(bdp, None, M)}
    return out


def dstate_specs(cfg: ModelConfig, mesh, batch_sharded: bool = True):
    dp = data_axes(mesh)
    if batch_sharded:
        pos_s, bt_s, kvp_s = P(dp), P(dp, None), P(dp, None, MODEL_AXIS)
    else:  # sequence parallelism: pages over dp, batch replicated
        pos_s, bt_s, kvp_s = P(), P(None, dp), P(None, dp, MODEL_AXIS)
    specs = {
        "pos": pos_s,
        "block_table": bt_s,
        "kv_pos": kvp_s,
        "units": mixer_state_specs(cfg, mesh, True, batch_sharded),
    }
    if cfg.ring_pages:
        specs["ring_table"], specs["ring_kv_pos"] = bt_s, kvp_s
    tail = {}
    for i, (mixer, _) in enumerate(cfg.tail_specs):
        sub = mixer_state_specs(cfg, mesh, False, batch_sharded)
        if f"l{i}" in sub:
            tail[f"t{i}"] = sub[f"l{i}"]
    specs["tail"] = tail
    return specs


# ---------------------------------------------------------------------------
# decode state construction
# ---------------------------------------------------------------------------
def make_dstate(cfg: ModelConfig, *, batch: int, max_seq: int,
                pages_per_shard: int | None = None, dp_shards: int = 1,
                dtype=None, ring_pages_per_shard: int | None = None):
    """Zero-initialized decode state (host-side; engine fills block tables).

    Full-attention layers hold ``max_seq / page`` table slots a lane and
    ``pages_per_shard`` arena pages; window layers a ring of
    ``cfg.ring_pages`` slots and ``ring_pages_per_shard`` pages.  Both
    counts default to every lane's slots backed, plus the dump page."""
    from ..layers import rglru, ssd
    dtype = dtype or cfg.dtype
    page = cfg.page_size

    def width(n):                         # divisible for seq-parallel
        return -(-n // dp_shards) * dp_shards

    def arena_pages(Pn, given):
        return given or max(batch // dp_shards, 1) * (
            Pn // dp_shards if batch < dp_shards else Pn) + 1

    # no full-attention layer: a vestigial table
    Pn = width(max(1, max_seq // page)) if cfg.has_full_attn else dp_shards
    R = width(cfg.ring_pages) if cfg.ring_pages else 0
    pages_g = arena_pages(Pn, pages_per_shard) * dp_shards
    ring_g = arena_pages(R, ring_pages_per_shard) * dp_shards

    def attn_state(n_units, mixer):
        K, dh = cfg.num_kv_heads, cfg.head_dim
        n = ring_g if mixer == "local_attn" else pages_g
        shape = (n, page, K, dh)
        sshape = (n, page, K)
        if n_units:
            shape = (n_units,) + shape
            sshape = (n_units,) + sshape
        if cfg.kv_dtype == "int8":
            return {"k": jnp.zeros(shape, jnp.int8),
                    "v": jnp.zeros(shape, jnp.int8),
                    "ks": jnp.zeros(sshape, jnp.float32),
                    "vs": jnp.zeros(sshape, jnp.float32)}
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    units, tail = {}, {}
    U = cfg.full_units
    for i, (mixer, _) in enumerate(cfg.pattern):
        if mixer in ("attn", "local_attn"):
            units[f"l{i}"] = attn_state(U, mixer)
        elif mixer == "mamba2":
            s = ssd.mamba2_init_state(cfg, batch)
            units[f"l{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (U,) + a.shape), s)
        elif mixer == "rglru":
            s = rglru.rglru_init_state(cfg, batch)
            units[f"l{i}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (U,) + a.shape), s)
    for i, (mixer, _) in enumerate(cfg.tail_specs):
        if mixer in ("attn", "local_attn"):
            tail[f"t{i}"] = attn_state(0, mixer)
        elif mixer == "mamba2":
            tail[f"t{i}"] = ssd.mamba2_init_state(cfg, batch)
        elif mixer == "rglru":
            tail[f"t{i}"] = rglru.rglru_init_state(cfg, batch)
    out = {
        "pos": jnp.zeros((batch,), jnp.int32),
        "block_table": jnp.full((batch, Pn), -1, jnp.int32),
        "kv_pos": jnp.full((batch, Pn, page), -1, jnp.int32),
        "units": units,
        "tail": tail,
    }
    if R:
        out["ring_table"] = jnp.full((batch, R), -1, jnp.int32)
        out["ring_kv_pos"] = jnp.full((batch, R, page), -1, jnp.int32)
    return out


# ---------------------------------------------------------------------------
# the step itself
# ---------------------------------------------------------------------------
def _tables(dstate) -> dict:
    """Each attention kind's (page table, slot positions) in ``dstate``."""
    return {kind: (dstate[bt], dstate[kvp])
            for kind, (bt, kvp) in TABLES.items() if bt in dstate}


def _apply_layer_tp(cfg, spec, p, x, pos, tables, state, seq_dp_axes=(),
                    unit=None):
    """One (mixer, ffn) layer; ``tables`` as ``_tables`` gives them.
    With ``unit`` the state is stacked over pattern units: attention
    indexes the stacked arena in place, a recurrent mixer reads and
    writes back its unit's slice."""
    mixer, ffn = spec
    M = MODEL_AXIS
    h = apply_norm(cfg.norm, p["norm1"], x)
    new_state = state
    if mixer in ("attn", "local_attn"):
        local = mixer == "local_attn"
        block_table, kv_pos = tables[mixer]
        scales = ((state["ks"], state["vs"])
                  if cfg.kv_dtype == "int8" else None)
        with jax.named_scope("window_attention" if local else "attention"):
            y, ak, av, kv_pos2, nsc = tpl.attn_decode_tp(
                cfg, p["attn"], h, pos, state["k"], state["v"], block_table,
                kv_pos, window=cfg.window if local else 0, axis=M,
                seq_dp_axes=seq_dp_axes, scales=scales, unit=unit)
        new_state = {"k": ak, "v": av}
        if nsc is not None:
            new_state["ks"], new_state["vs"] = nsc
    elif mixer in ("mamba2", "rglru"):
        decode = (tpl.mamba2_decode_tp if mixer == "mamba2"
                  else tpl.rglru_decode_tp)
        weights = p["ssd" if mixer == "mamba2" else "rglru"]
        if unit is None:
            y, new_state = decode(cfg, weights, h, state, M)
        else:
            y, ns = decode(cfg, weights, h, jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, unit, 0, False),
                state), M)
            new_state = jax.tree.map(
                lambda a, n: lax.dynamic_update_index_in_dim(a, n, unit, 0),
                state, ns)
    x = x + y
    if ffn != "none":
        h = apply_norm(cfg.norm, p["norm2"], x)
        with jax.named_scope("ffn"):
            if ffn == "moe":
                x = x + tpl.moe_decode_tp(cfg, p["ffn"], h, M)
            else:
                x = x + tpl.mlp_decode_tp(cfg, p["ffn"], h, M)
    return x, new_state


def _apply_units(cfg, seq_dp_axes, units_p, units_s, x, pos, tables):
    """The layer loop over pattern units.  The unit-stacked state rides in
    ``lax.scan``'s carry, so every layer updates it in place: no layer
    slices its arena out and no step copies the arena."""

    def body(carry, inp):
        x, units_s = carry
        unit_p, u = inp
        units_s = dict(units_s)
        for i, spec in enumerate(cfg.pattern):
            key = f"l{i}"
            x, units_s[key] = _apply_layer_tp(
                cfg, spec, unit_p[key], x, pos, tables, units_s[key],
                seq_dp_axes, unit=u)
        return (x, units_s), None

    (x, units_s), _ = lax.scan(
        body, (x, units_s), (units_p, jnp.arange(cfg.full_units)))
    return x, units_s


def _decode_local(cfg: ModelConfig, seq_dp_axes, params, dstate, tokens,
                  return_logits: bool = False, vocab_sharded: bool = True):
    """Runs per-device inside shard_map."""
    M = MODEL_AXIS
    pos = dstate["pos"]
    tables = _tables(dstate)
    x = tpl.embed_tp(params["embed"], tokens, M, sharded=vocab_sharded)
    x, new_units = _apply_units(cfg, seq_dp_axes, params["units"],
                                dstate["units"], x, pos, tables)
    new_tail = {}
    for i, spec in enumerate(cfg.tail_specs):
        st = dstate["tail"].get(f"t{i}")
        x, ns = _apply_layer_tp(cfg, spec, params["tail"][f"t{i}"], x, pos,
                                tables, st, seq_dp_axes)
        if ns is not None:
            new_tail[f"t{i}"] = ns
    x = apply_norm(cfg.norm, params["final_norm"], x)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    with jax.named_scope("logits"):
        logits_loc = tpl.logits_tp(table, x, M)
        next_tok = tpl.greedy_sample_tp(logits_loc, M,
                                        sharded=vocab_sharded)

    # one new token is now resident at position pos for every sequence:
    # advance position; mark its slot in each table's kv_pos (idempotent
    # w.r.t. layers)
    b_ix = jnp.arange(pos.shape[0])
    out_state = dict(dstate, pos=pos + 1, units=new_units, tail=new_tail)
    for kind, (_, kvp_key) in TABLES.items():
        if kind not in tables:
            continue
        kv_pos = tables[kind][1]
        mine, lpage, lslot = tpl.table_slot(
            pos, kv_pos.shape[1], kv_pos.shape[-1], M, seq_dp_axes,
            ring=kind == "local_attn")
        out_state[kvp_key] = kv_pos.at[b_ix, lpage, lslot].set(
            jnp.where(mine, pos, kv_pos[b_ix, lpage, lslot]))
    if return_logits:
        full = (lax.all_gather(logits_loc, MODEL_AXIS, axis=1, tiled=True)
                if vocab_sharded else logits_loc)
        return out_state, next_tok, full
    return out_state, next_tok


def make_decode_step(cfg: ModelConfig, mesh, params_shape, *,
                     batch_sharded: bool = True, return_logits: bool = False):
    """Build the jitted serve_step: (params, dstate, tokens) → (dstate', tok).

    ``batch_sharded=False`` switches to sequence-parallel mode for
    global_batch < #data-shards (the long_500k shape): pages are spread
    over the data axes and the attention merge spans (data + model).
    """
    dp = data_axes(mesh)
    tp = mesh.shape[MODEL_AXIS]
    vocab_sharded = cfg.vocab_size % tp == 0
    pspecs = serve_param_specs(cfg, params_shape, tp)
    sspecs = dstate_specs(cfg, mesh, batch_sharded)
    tok_spec = P(dp) if batch_sharded else P()
    seq_dp_axes = () if batch_sharded else dp

    out_specs = (sspecs, tok_spec)
    if return_logits:
        out_specs = out_specs + ((P(dp, None) if batch_sharded
                                  else P(None, None)),)
    fn = shard_map(
        functools.partial(_decode_local, cfg, seq_dp_axes,
                          return_logits=return_logits,
                          vocab_sharded=vocab_sharded),
        mesh=mesh,
        in_specs=(pspecs, sspecs, tok_spec),
        out_specs=out_specs,
    )
    return jax.jit(fn, donate_argnums=(1,)), pspecs, sspecs
