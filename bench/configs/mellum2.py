"""Plain float32 reference for Mellum2-12B-A2.5B (and its held expert
share): pre-RMSNorm blocks of grouped-KV causal attention, window layers
(sliding window, plain RoPE) and full layers (YaRN RoPE) in the
configuration's pattern, each followed by a sparse SwiGLU expert layer
(float32 softmax router over every routed expert, top-k renormalised,
only the held experts computed: an expert held elsewhere adds nothing,
as on the chip that holds this share), a final RMSNorm and an untied
output head.

It imports nothing of the program under test.  Its weights are drawn
again from the run's seed by the recipe the serving launcher uses
(``jax.random.split`` tree, normal scaled by the fan-in, rounded to the
configured dtype; unit norms), layer by layer.  Matmuls run at HIGHEST
precision.  Sequences are computed one at a time, attention in blocks
of queries (window layers read only their window of keys), the head in
blocks of positions, so a 6.7k-token sequence fits beside a layer's
weights.

YaRN is written out here from arXiv:2309.00071 and HF ``transformers``'
``_compute_yarn_parameters``.  Departures from the published model: no
multi-token-prediction head (serving does not use it); softmax routing
(``config.json`` names no scoring function).

``served_gaps`` runs it over finished requests and returns, for every
served token, how far its reference logit lies below the reference's
best at that position.  With ``control=True`` it also runs the same
forward in fp8 (e4m3, power-of-two scales per tensor on weights, per
row on activations, float32 accumulation) and returns the gap of the
token the fp8 forward puts first: the control a comparison has to fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
EPS = 1e-6
Q_BLOCK = 512            # queries (and head positions) per block
BUCKET = 1024            # sequences are padded to a multiple of this


def _check(model: dict) -> None:
    want = dict(norm="rmsnorm", mlp="swiglu", use_rope=True,
                tie_embeddings=False, qkv_bias=False)
    for k, v in want.items():
        if model.get(k, v) != v:
            raise ValueError(f"reference does not cover {k}={model.get(k)}")
    for mixer, ffn in model["pattern"]:
        if mixer not in ("attn", "local_attn") or ffn != "moe":
            raise ValueError(f"reference does not cover ({mixer}, {ffn})")


# ------------------------------------------------------------- weights
def _normal(key, shape, dtype, scale):
    w = (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
    return w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_weights(shape_key, dtype, key):
    d, h, kvh, dh, f, e_routed, e_held = shape_key
    kmix, kffn, _, _ = jax.random.split(key, 4)
    kq, kk, kv, ko, _ = jax.random.split(kmix, 5)
    kr, k1, k2, k3 = jax.random.split(kffn, 4)
    return {"wq": _normal(kq, (d, h * dh), dtype, d ** -0.5),
            "wk": _normal(kk, (d, kvh * dh), dtype, d ** -0.5),
            "wv": _normal(kv, (d, kvh * dh), dtype, d ** -0.5),
            "wo": _normal(ko, (h * dh, d), dtype, (h * dh) ** -0.5),
            "router": _normal(kr, (d, e_routed), jnp.float32, d ** -0.5),
            "wi": _normal(k1, (e_held, d, f), dtype, d ** -0.5),
            "wg": _normal(k2, (e_held, d, f), dtype, d ** -0.5),
            "we": _normal(k3, (e_held, f, d), dtype, f ** -0.5)}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _table(v, d, dtype, key):
    return _normal(key, (v, d), dtype, 1.0 / d ** 0.5)


def _shape_key(m):
    held = m["num_experts"]
    return (m["d_model"], m["num_heads"], m["num_kv_heads"], m["head_dim"],
            m["d_ff"], m.get("router_experts") or held, held)


def _keys(seed: int, model: dict):
    """Per-layer keys in layer order, and the embedding's and head's."""
    n_pat = len(model["pattern"])
    ku, _, ke, _, kf = jax.random.split(jax.random.PRNGKey(seed), 5)
    units = jax.random.split(ku, model["num_layers"] // n_pat)
    layer = [k for u in units for k in jax.random.split(u, n_pat)]
    return layer, ke, kf


# ------------------------------------------------------------- rope
def yarn_inv_freq(dh, theta, factor, orig, beta_fast, beta_slow):
    def corr(rot):
        return dh * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dh - 1)
    if low == high:
        high += 0.001
    base = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ramp = np.clip((np.arange(dh // 2) - low) / (high - low), 0, 1)
    extrapolation = 1.0 - ramp
    return (base / factor * ramp + base * extrapolation).astype(np.float32)


def _rope_params(model: dict, local: bool):
    """(inverse frequencies, cos/sin factor) of a layer kind."""
    dh, theta = model["head_dim"], float(model["rope_theta"])
    if local or not model.get("yarn_factor"):
        inv = (1.0 / theta ** (np.arange(0, dh, 2) / dh)).astype(np.float32)
        return tuple(inv.tolist()), 1.0
    inv = yarn_inv_freq(dh, theta, model["yarn_factor"],
                        model["yarn_original_max_pos"],
                        model["yarn_beta_fast"], model["yarn_beta_slow"])
    return tuple(inv.tolist()), float(model["yarn_attention_factor"])


def _rope(x, inv, scale):
    s, _, dh = x.shape
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)
    cos = (jnp.cos(ang) * scale)[:, None]
    sin = (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ------------------------------------------------------------- forward
def _q8(x, axis=None):
    """Round to fp8 e4m3 under a power-of-two scale (per tensor, or per
    slice along ``axis``); returns float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / 448.0)))
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, w, fp8: bool):
    if fp8:
        return jnp.einsum(eq, _q8(a, -1).astype(jnp.bfloat16),
                          w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a, w, precision=HI)


def _rms(x):
    return x * lax.rsqrt((x * x).mean(-1, keepdims=True) + EPS)


def _attend(q, k, v, window: int, fp8: bool):
    """Causal attention of q [S, h, dh] over k, v [S, kvh, dh], in blocks
    of queries; a window layer reads only the keys of its window."""
    s, h, dh = q.shape
    kvh = k.shape[1]
    qb = min(Q_BLOCK, s)
    span = qb + window if window else s
    if window:                 # keys before position 0 are padding
        k = jnp.pad(k, ((window, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((window, 0), (0, 0), (0, 0)))

    def block(b):
        q0 = b * qb
        qg = lax.dynamic_slice_in_dim(q, q0, qb).reshape(qb, kvh, h // kvh, dh)
        k0 = q0 if window else 0          # first key row (padded rows)
        kb = lax.dynamic_slice_in_dim(k, k0, span)
        vb = lax.dynamic_slice_in_dim(v, k0, span)
        kpos = jnp.arange(span) + (q0 - window if window else 0)
        qpos = q0 + jnp.arange(qb)
        ok = (kpos[None] <= qpos[:, None]) & (kpos[None] >= 0)
        if window:
            ok = ok & (kpos[None] > qpos[:, None] - window)
        sc = jnp.einsum("qkgd,tkd->kgqt", qg, kb, precision=HI) * dh ** -0.5
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        if fp8:
            p = _q8(p, -1)
        return jnp.einsum("kgqt,tkd->qkgd", p, vb, precision=HI)

    out = lax.map(block, jnp.arange(s // qb))
    return out.reshape(s, h * dh)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _block(shape_key, window, inv, scale, top_k, first, fp8, w, x):
    """One (attention, expert) layer over one padded sequence x [S, d]."""
    d, h, kvh, dh, _, _, e_held = shape_key
    if fp8:
        w = {k: (_q8(a) if a.ndim == 2 else _q8(a, (1, 2)))
             for k, a in w.items()}
    s = x.shape[0]
    a = _rms(x)
    q = _rope(_mm("sd,de->se", a, w["wq"], fp8).reshape(s, h, dh), inv, scale)
    k = _rope(_mm("sd,de->se", a, w["wk"], fp8).reshape(s, kvh, dh), inv,
              scale)
    v = _mm("sd,de->se", a, w["wv"], fp8).reshape(s, kvh, dh)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    x = x + _mm("se,ed->sd", _attend(q, k, v, window, fp8), w["wo"], fp8)
    a = _rms(x)
    probs = jax.nn.softmax(_mm("sd,de->se", a, w["router"], fp8), axis=-1)
    gate, idx = lax.top_k(probs, top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    dense = jnp.zeros_like(probs).at[jnp.arange(s)[:, None], idx].set(gate)
    held = dense[:, first:first + e_held]               # [S, E_held]
    hi = _mm("sd,edf->esf", a, w["wi"], fp8)
    hg = _mm("sd,edf->esf", a, w["wg"], fp8)
    y = _mm("esf,efd->esd", jax.nn.silu(hg) * hi, w["we"], fp8)
    return x + jnp.einsum("esd,se->sd", y, held, precision=HI)


@functools.partial(jax.jit, static_argnums=(0,))
def _head_gaps(fp8, x, table, next_tokens, ctrl_x):
    """Per position, in blocks: best reference logit minus the reference
    logit of the next token, and minus the reference logit of the
    control's top token."""
    s = x.shape[0]
    qb = min(Q_BLOCK, s)

    def block(b):
        xa = _rms(lax.dynamic_slice_in_dim(x, b * qb, qb))
        ref = jnp.einsum("sd,vd->sv", xa, table, precision=HI)
        best = ref.max(-1)
        nt = lax.dynamic_slice_in_dim(next_tokens, b * qb, qb)
        served = jnp.take_along_axis(ref, nt[:, None], -1)[:, 0]
        xc = _rms(lax.dynamic_slice_in_dim(ctrl_x, b * qb, qb))
        if fp8:
            ctl = jnp.einsum("sd,vd->sv", _q8(xc, -1).astype(jnp.bfloat16),
                             _q8(table).astype(jnp.bfloat16),
                             preferred_element_type=jnp.float32)
        else:
            ctl = ref
        top = jnp.argmax(ctl, -1)
        ctrl = jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
        return best - served, best - ctrl

    g, c = lax.map(block, jnp.arange(s // qb))
    return g.reshape(s), c.reshape(s)


def _layers(model: dict, seed: int):
    """(layer weights, static block arguments) for every layer in order."""
    skey = _shape_key(model)
    dtype = jnp.dtype(model["dtype"])
    keys, ke, kf = _keys(seed, model)
    pat = model["pattern"]
    for i, key in enumerate(keys):
        local = pat[i % len(pat)][0] == "local_attn"
        inv, scale = _rope_params(model, local)
        yield (lambda key=key: _layer_weights(skey, dtype, key),
               (skey, model["window"] if local else 0, inv, scale,
                model["top_k"], model.get("expert_first", 0)))


def forward_hidden(model: dict, seed: int, seqs, *, fp8: bool = False):
    """Last hidden states (before the final norm) of each token sequence,
    all padded to one length (a multiple of ``BUCKET``), so that a run
    compiles each layer kind once."""
    _check(model)
    dtype = jnp.dtype(model["dtype"])
    _, ke, _ = _keys(seed, model)
    emb = _table(model["vocab_size"], model["d_model"], dtype, ke)
    S = -(-max(map(len, seqs)) // BUCKET) * BUCKET
    xs = [emb[jnp.asarray(np.pad(np.asarray(t, np.int32), (0, S - len(t))))]
          for t in seqs]
    del emb
    for weights, static in _layers(model, seed):
        w = weights()
        xs = [_block(*static, fp8, w, x) for x in xs]
        del w
    return xs


def head_table(model: dict, seed: int):
    _, _, kf = _keys(seed, model)
    return _table(model["vocab_size"], model["d_model"],
                  jnp.dtype(model["dtype"]), kf)


def reference_logits(model: dict, seed: int, tokens) -> np.ndarray:
    """Float32 logits [len(tokens), V] of one sequence (small sizes)."""
    x = forward_hidden(model, seed, [tokens])[0][:len(tokens)]
    return np.asarray(jnp.einsum("sd,vd->sv", _rms(x),
                                 head_table(model, seed), precision=HI))


def served_gaps(model: dict, seed: int, seqs, starts, *,
                control: bool = False):
    """``seqs[i]`` is a prompt followed by its served tokens, which
    begin at index ``starts[i]``.  Returns (gaps, control gaps) as lists
    of float arrays, one per sequence; control gaps are None unless
    ``control``."""
    xs = forward_hidden(model, seed, seqs)
    xc = forward_hidden(model, seed, seqs, fp8=True) if control else xs
    table = head_table(model, seed)
    gaps, cgaps = [], []
    for t, x, c, n0 in zip(seqs, xs, xc, starts):
        n = len(t)
        nxt = np.pad(np.asarray(t[1:], np.int32), (0, x.shape[0] - n + 1))
        g, cg = _head_gaps(control, x, table, jnp.asarray(nxt), c)
        # logits at position p predict token p + 1
        gaps.append(np.asarray(g)[n0 - 1:n - 1])
        cgaps.append(np.asarray(cg)[n0 - 1:n - 1] if control else None)
    return gaps, (cgaps if control else None)
