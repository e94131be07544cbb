"""Decode-vs-oracle parity and the paged serving engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.runtime import make_host_mesh
from repro.serving import decode as dec
from repro.serving.engine import PAGE_CLS, ServingEngine


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


def _fill_tables(ds, order=None):
    """Back every slot of every page table in ``ds`` (the full-attention
    table and, with window layers, the ring table) with its own page of
    that table's arena: in order, or in the order ``order(n)`` gives."""
    for bt, kind in (("block_table", "attn"), ("ring_table", "local_attn")):
        if bt in ds:
            B, Pn = ds[bt].shape
            ids = np.arange(B * Pn) if order is None else order(B * Pn)
            ds[bt] = jnp.asarray(ids.astype(np.int32).reshape(B, Pn))
    return ds


def _parity(cfg, mesh, S=24, tol=2e-2):
    key = jax.random.PRNGKey(1)
    params = T.init_params(cfg, key)
    B = 2
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    logits_full, _ = T.forward(cfg, params, {"tokens": toks})
    pshape = jax.eval_shape(lambda: params)
    step, _, _ = dec.make_decode_step(cfg, mesh, pshape, return_logits=True)
    ds = _fill_tables(dec.make_dstate(cfg, batch=B, max_seq=64,
                                      dp_shards=1))
    errs = []
    for t in range(S):
        ds, tok, lg = step(params, ds, toks[:, t])
        errs.append(float(jnp.abs(lg - logits_full[:, t]).max()))
    rel = max(errs) / (float(jnp.abs(logits_full).max()) + 1e-9)
    assert rel < tol, rel


@pytest.mark.parametrize("arch,fp32", [
    ("qwen2_5_32b", False),            # GQA + bias + rope
    ("granite_20b", False),            # MQA kv=1
    ("mamba2_370m", False),            # recurrent state decode
    ("recurrentgemma_9b", True),       # hybrid (bf16 assoc-scan noise)
    ("granite_moe_3b_a800m", True),    # MoE (top-k routing is discrete)
    ("moonshot_v1_16b_a3b", True),
])
def test_decode_matches_oracle(arch, fp32, mesh):
    cfg = get_smoke_config(arch)
    if fp32:
        cfg = dataclasses.replace(cfg, dtype=jnp.float32,
                                  capacity_factor=100.0)
    # bf16 tolerance: recurrent-state archs accumulate rounding over the
    # whole sequence and the exact noise floor shifts between XLA releases
    # (observed on the CPU with jax 0.9.0: 1.5e-2 for mamba2, 1.4e-2 for
    # qwen2.5, 8.9e-3 for granite-20b)
    _parity(cfg, mesh, tol=1e-3 if fp32 else 3e-2)


def _units_by_layer(cfg, seq_dp_axes, units_p, units_s, x, pos, tables):
    """Reference layer loop: each unit's weights and 4-D arena taken out
    of the stack, every layer applied one by one, the states restacked."""
    per_unit = []
    for u in range(cfg.full_units):
        unit_p = jax.tree.map(lambda a: a[u], units_p)
        unit_s = jax.tree.map(lambda a: a[u], units_s)
        for i, spec in enumerate(cfg.pattern):
            x, unit_s[f"l{i}"] = dec._apply_layer_tp(
                cfg, spec, unit_p[f"l{i}"], x, pos, tables,
                unit_s[f"l{i}"], seq_dp_axes)
        per_unit.append(unit_s)
    return x, jax.tree.map(lambda *a: jnp.stack(a), *per_unit)


def _served_decode(cfg, mesh, params, toks, steps):
    """``steps`` decode steps of ``toks`` on two lanes whose pages are
    scattered over the arena; every output and the final state."""
    pshape = jax.eval_shape(lambda: params)
    step, _, _ = dec.make_decode_step(cfg, mesh, pshape, return_logits=True)
    ds = _fill_tables(dec.make_dstate(cfg, batch=2, max_seq=32, dp_shards=1),
                      np.random.default_rng(0).permutation)
    outs = []
    for t in range(steps):
        ds, tok, lg = step(params, ds, toks[:, t])
        outs.append((tok, lg))
    return jax.device_get((outs, ds))


def _with_attn_tail(cfg):
    return dataclasses.replace(cfg, num_layers=5,
                               pattern=(("attn", "mlp"), ("attn", "mlp")))


@pytest.mark.parametrize("arch,change", [
    ("starcoder2_3b", None),                   # GQA + bias
    ("granite_20b", None),                     # MQA
    ("starcoder2_3b", dict(kv_dtype="int8")),  # int8 K/V + scales
    ("recurrentgemma_9b", dict(num_layers=7)),  # local_attn + rglru, tail
    ("mamba2_370m", None),
    ("qwen2_5_32b", "attn_tail"),              # a 4-D arena in the tail
    ("mellum2_12b_a2_5b", dict(window=8)),     # ring + full tables, experts
], ids=["starcoder2", "granite-mqa", "int8-kv", "recurrentgemma",
        "mamba2", "attn-tail", "mellum2"])
def test_decode_in_place_matches_per_layer_loop(arch, change, mesh):
    """The served step, which carries the stacked state through the
    layer loop and indexes it in place, gives bit for bit the tokens,
    logits, positions and state of a per-layer loop over each unit's own
    arena, across two page boundaries."""
    # float32: in bf16 XLA may keep a fused intermediate in float32
    # (excess precision), so two programs with the same arithmetic but
    # different fusions can round differently
    cfg = dataclasses.replace(get_smoke_config(arch), page_size=4,
                              dtype=jnp.float32)
    if change == "attn_tail":
        cfg = _with_attn_tail(cfg)
    elif change:
        cfg = dataclasses.replace(cfg, **change)
    assert cfg.full_units >= 2
    params = T.init_params(cfg, jax.random.PRNGKey(2))
    steps = 10                                 # positions 0-9: pages 0-2
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, steps), 0,
                              cfg.vocab_size)
    got = _served_decode(cfg, mesh, params, toks, steps)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(dec, "_apply_units", _units_by_layer)
        want = _served_decode(cfg, mesh, params, toks, steps)
    got_leaves, got_tree = jax.tree.flatten(got)
    want_leaves, want_tree = jax.tree.flatten(want)
    assert got_tree == want_tree
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    _, ds = got
    # every position is marked in the table its attention layers read
    kvp = ds["ring_kv_pos"] if cfg.ring_pages else ds["kv_pos"]
    assert cfg.attn_layers == 0 or (kvp >= 0).sum() == 2 * steps


def test_engine_generate_evict_recover(mesh):
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=4, max_seq=64)
    l0 = eng.add_request([5, 9, 3])
    l1 = eng.add_request([7, 7])
    for _ in range(16):
        eng.step()
    assert len(eng.sessions[l0].tokens) > 10
    # crash: all transient allocator metadata lost; GC rebuilds it
    stats = eng.crash_and_recover()
    assert stats["live_before"] == stats["live_after"] == stats["marked"]
    before = list(eng.sessions[l0].tokens)
    for _ in range(5):
        eng.step()
    assert eng.sessions[l0].tokens[:len(before)] == before
    assert len(eng.sessions[l0].tokens) == len(before) + 5
    # eviction frees pages; lane is reusable
    eng.finish(l0)
    l2 = eng.add_request([1, 2, 3])
    for _ in range(6):
        eng.step()
    assert len(eng.sessions[l2].tokens) > 3


def test_engine_page_accounting(mesh):
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("starcoder2_3b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=2, max_seq=48)
    l0 = eng.add_request([3, 1, 4])
    for _ in range(20):
        eng.step()
    live = ja.live_blocks(eng.astate, eng.acfg)[0]
    pos = int(np.asarray(eng.dstate["pos"][l0]))
    expected = -(-pos // cfg.page_size)
    assert live == expected, (live, expected)
    eng.finish(l0)
    assert ja.live_blocks(eng.astate, eng.acfg)[0] == 0


def test_engine_oversized_prompt_span(mesh):
    """A prompt whose page table exceeds one superblock reserves one
    contiguous large-object span, survives crash recovery mid-prompt,
    and returns every superblock on eviction."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=2, max_seq=256,
                        pages_per_sb=16)
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=200)]
    lane = eng.add_request(prompt)         # 25 pages > 16 per superblock
    assert lane in eng.large_spans
    off, n_span = eng.large_spans[lane]
    assert n_span == 32                    # decode-ahead: max_seq pages
    lb = ja.live_blocks(eng.astate, eng.acfg)
    assert lb["large"] == 1 and lb[0] == 0
    bt = np.asarray(eng.dstate["block_table"][lane])
    assert bt[:32].tolist() == list(range(off, off + 32))

    # a short request coexists: its lazily-allocated pages never overlap
    other = eng.add_request([5, 9, 3])
    for _ in range(20):
        eng.step()
    pages_other = np.asarray(eng.dstate["block_table"][other])
    pages_other = pages_other[pages_other >= 0]
    assert not set(pages_other.tolist()) & set(range(off, off + 32))

    # crash mid-prompt: the span survives the vectorized mark–sweep
    before = list(eng.sessions[lane].tokens)
    eng.crash_and_recover()
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 1
    for _ in range(5):
        eng.step()
    assert eng.sessions[lane].tokens[:len(before)] == before

    # eviction frees the whole span; the superblocks are reusable
    eng.finish(lane)
    eng.finish(other)
    lb = ja.live_blocks(eng.astate, eng.acfg)
    assert lb["large"] == 0 and lb[0] == 0
    assert lane not in eng.large_spans


def test_engine_decode_ahead_no_mid_decode_alloc(mesh):
    """Decode-ahead reservation: a span-reserved sequence is sized to
    max_seq up front, so decoding past the prompt never allocates a page
    mid-decode (no lazy page, no span migration)."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=1, max_seq=64,
                        pages_per_sb=4)
    rng = np.random.default_rng(1)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=40)]
    lane = eng.add_request(prompt)         # 5 prompt pages > 4 per sb
    off, n_span = eng.large_spans[lane]
    assert n_span == 64 // 8               # max_seq pages, not the prompt's 5
    bt = np.asarray(eng.dstate["block_table"][lane])
    assert bt[:n_span].tolist() == list(range(off, off + n_span))
    for _ in range(45):                    # cross the prompt→decode boundary
        eng.step()
    assert int(np.asarray(eng.dstate["pos"][lane])) > len(prompt)
    # every page the decode touched was pre-backed by the span: the
    # per-page allocator never ran
    assert ja.live_blocks(eng.astate, eng.acfg)[0] == 0
    eng.finish(lane)
    lb = ja.live_blocks(eng.astate, eng.acfg)
    assert lb["large"] == 0 and lb[0] == 0


def test_engine_all_lanes_fit_decode_ahead_spans(mesh):
    """Arena sizing regression: every lane can hold a decode-ahead span
    at once — the superblock rounding of spans must be provisioned per
    lane, not absorbed by per-page slack."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=3, max_seq=128,
                        pages_per_sb=4)
    rng = np.random.default_rng(2)
    lanes = []
    for _ in range(3):                     # 5 prompt pages > 4 per sb each
        prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=40)]
        lanes.append(eng.add_request(prompt))
    assert all(l in eng.large_spans for l in lanes)
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 3
    spans = sorted(eng.large_spans[l] for l in lanes)
    for (a, na), (b, _) in zip(spans, spans[1:]):
        assert a + na <= b                 # reserved spans are disjoint
    for l in lanes:
        eng.finish(l)
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 0


def test_engine_span_prefix_sharing(mesh):
    """Cross-lane prefix span sharing: a published oversized-prompt span
    is *acquired* by later matching requests (one refcount each — no page
    copy, no fresh reservation), survives crash recovery with its
    refcount GC-reconstructed from the lanes' roots, and frees only when
    the last holder exits."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=3, max_seq=64,
                        pages_per_sb=4)
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=40)]

    a = eng.add_request(prompt, share_prefix=True)   # miss → reserves a span
    assert a in eng.large_spans
    off, n_span = eng.large_spans[a]
    head_sb = off // eng.acfg.sb_words
    for _ in range(len(prompt)):
        eng.step()
    eng.publish_prefix(a)
    # owner reference + the prefix cache's reference
    assert int(eng.astate.span_refs[head_sb]) == 2
    # re-publishing the same prefix must not stack cache references:
    # the entry holds exactly one
    eng.publish_prefix(a)
    assert int(eng.astate.span_refs[head_sb]) == 2

    b = eng.add_request(prompt, share_prefix=True)   # hit → acquire, no copy
    assert b in eng.shared_spans and b not in eng.large_spans
    assert int(eng.astate.span_refs[head_sb]) == 3
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 1  # ONE span
    assert int(np.asarray(eng.dstate["pos"][b])) == len(prompt)
    full = len(prompt) // cfg.page_size
    bt_b = np.asarray(eng.dstate["block_table"][b])
    assert bt_b[:full].tolist() == list(range(off, off + full))

    # both lanes decode past the prefix; the sharer's fresh pages come
    # from the per-page allocator, never from inside the span
    for _ in range(10):
        eng.step()
    own_b = np.asarray(eng.dstate["block_table"][b])
    own_b = own_b[own_b >= 0][full:]
    assert own_b.size and not (set(own_b.tolist())
                               & set(range(off, off + n_span)))

    # crash: transient refcounts are lost; GC reconstructs them from the
    # two lanes' roots PLUS the durable index record — the cache's lease
    # now survives the crash (tentpole: crash-surviving cache keys)
    stats = eng.crash_and_recover()
    assert stats["index_records"] == 1
    assert int(eng.astate.span_refs[head_sb]) == 3
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 1
    # recounted per-page refs never cover span-backed pages — a stale
    # entry would pin the offset after the span frees and is reallocated
    assert not (set(eng.page_refs)
                & set(range(off, off + n_span)))
    tokens_b = list(eng.sessions[b].tokens)
    for _ in range(3):
        eng.step()
    assert eng.sessions[b].tokens[:len(tokens_b)] == tokens_b

    # the record already re-published the entry: publishing again is a
    # no-op (the cache holds exactly one reference per entry)
    eng.publish_prefix(b)
    assert int(eng.astate.span_refs[head_sb]) == 3
    eng.drop_prefix_cache()              # cache lease + index record out
    assert int(eng.astate.span_refs[head_sb]) == 2
    # a *sharer* can publish anew after the drop: the entry takes one
    # span reference via the span path (never the per-page path — that
    # would refcount span-interior pages)
    eng.publish_prefix(b)
    assert int(eng.astate.span_refs[head_sb]) == 3
    assert not (set(eng.page_refs) & set(range(off, off + n_span)))
    eng.drop_prefix_cache()                          # cache ref released
    assert int(eng.astate.span_refs[head_sb]) == 2

    eng.finish(a)                                    # sharer keeps the span
    assert int(eng.astate.span_refs[head_sb]) == 1
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 1
    bt_b = np.asarray(eng.dstate["block_table"][b])
    assert bt_b[:full].tolist() == list(range(off, off + full))
    eng.finish(b)                                    # last holder → freed
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 0
    assert int(eng.astate.span_refs[head_sb]) == 0
    lb = ja.live_blocks(eng.astate, eng.acfg)
    assert lb[0] == 0                                # lazy pages freed too


def test_engine_owner_exit_frees_decode_ahead_tail(mesh):
    """Tentpole at the engine level: publish/acquire hold only *prefix*
    leases, so when the reserving lane finishes short, the decode-ahead
    tail of its span frees immediately — reusable by the next
    reservation — while the shared prefix stays placed for the sharer."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=3, max_seq=64,
                        pages_per_sb=2)
    rng = np.random.default_rng(5)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=24)]

    a = eng.add_request(prompt, share_prefix=True)
    off, n_span = eng.large_spans[a]
    head_sb = off // eng.acfg.sb_words
    ext = ja.span_sbs(eng.acfg, n_span)
    for _ in range(len(prompt)):
        eng.step()
    eng.publish_prefix(a)
    full = len(prompt) // cfg.page_size
    lease_sbs = -(-full // eng.acfg.sb_words)
    assert lease_sbs < ext                 # there IS a decode-ahead tail
    # prefix leases: head range carries owner+cache, the tail only the owner
    refs = np.asarray(eng.astate.span_refs)
    assert refs[head_sb] == 2
    assert refs[head_sb + ext - 1] == 1

    b = eng.add_request(prompt, share_prefix=True)   # prefix lease, no copy
    assert eng.shared_spans[b] == (off, full, lease_sbs)
    free_before = int(np.asarray(
        eng.astate.sb_class == ja.FREE_CLS)[:int(eng.astate.used_sbs)].sum())

    eng.finish(a)                          # owner exits: tail must free NOW
    cls = np.asarray(eng.astate.sb_class)
    tail = list(range(head_sb + lease_sbs, head_sb + ext))
    assert all(cls[s] == ja.FREE_CLS for s in tail), \
        "decode-ahead tail still pinned after the owner's release"
    assert cls[head_sb] == ja.LARGE_CLS    # shared prefix stays placed
    assert int(ja.span_sbs(eng.acfg, int(
        eng.astate.sb_block_words[head_sb]))) == lease_sbs
    free_after = int(np.asarray(
        eng.astate.sb_class == ja.FREE_CLS)[:int(eng.astate.used_sbs)].sum())
    assert free_after - free_before >= ext - lease_sbs
    # the sharer still decodes correctly off the shared prefix
    for _ in range(5):
        eng.step()
    bt_b = np.asarray(eng.dstate["block_table"][b])
    assert bt_b[:full].tolist() == list(range(off, off + full))
    # last holders out: cache, then the sharer — everything frees
    eng.drop_prefix_cache()
    eng.finish(b)
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 0
    assert int(np.asarray(eng.astate.span_refs).sum()) == 0


def test_engine_prefix_index_survives_crash(mesh):
    """Tentpole acceptance: a published prefix survives
    ``crash_and_recover`` through the durable index — cache-hittable
    without re-prefill — and the recovered lease vector equals the
    pre-crash *trimmed* one: the record's and each live sharer's leases
    re-trim to their page-derived superblock counts instead of the
    conservative full extent."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=4, max_seq=64,
                        pages_per_sb=2)
    rng = np.random.default_rng(11)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=24)]

    a = eng.add_request(prompt, share_prefix=True)   # miss → reserves a span
    off, n_span = eng.large_spans[a]
    head_sb = off // eng.acfg.sb_words
    ext = ja.span_sbs(eng.acfg, n_span)
    for _ in range(len(prompt)):
        eng.step()
    eng.publish_prefix(a)                            # cache lease + record
    full = len(prompt) // cfg.page_size
    lease_sbs = -(-full // eng.acfg.sb_words)
    assert lease_sbs < ext                 # there IS a decode-ahead tail
    b = eng.add_request(prompt, share_prefix=True)   # sharer: prefix lease
    c = eng.add_request(prompt)                      # control (own span)
    for _ in range(len(prompt) + 4):       # control decodes past its prompt
        eng.step()
    refs_before = np.asarray(eng.astate.span_refs).copy()
    assert refs_before[head_sb] == 3       # owner + cache + sharer
    assert refs_before[head_sb + ext - 1] == 1       # tail: owner only

    stats = eng.crash_and_recover()
    assert stats["index_records"] == 1
    # acceptance: lease vector == pre-crash trimmed extents, NOT the
    # conservative full-extent reconstruction (which would be 3s across)
    assert np.asarray(eng.astate.span_refs).tolist() == \
        refs_before.tolist(), "post-recovery lease vector drifted"

    # acceptance: the published prefix is cache-hittable without
    # re-prefill — no fresh reservation, the request starts at the
    # prompt boundary on the recovered span
    spans_live = ja.live_blocks(eng.astate, eng.acfg)["large"]
    d = eng.add_request(prompt, share_prefix=True)
    assert d in eng.shared_spans and d not in eng.large_spans
    assert int(np.asarray(eng.dstate["pos"][d])) == len(prompt)
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == spans_live
    bt_d = np.asarray(eng.dstate["block_table"][d])
    assert bt_d[:full].tolist() == list(range(off, off + full))
    # …and decodes correctly off the recovered prefix (parity vs the
    # control lane, which prefilled the same prompt itself)
    for _ in range(4):
        eng.step()
    assert eng.sessions[d].tokens[len(prompt):] == \
        eng.sessions[c].tokens[len(prompt):len(eng.sessions[d].tokens)]

    # owner exit durably trims the tail; a second crash recovers the
    # trimmed extent as-is (record re-trim is a no-op at equal extents)
    eng.finish(a)
    refs_trimmed = np.asarray(eng.astate.span_refs).copy()
    assert refs_trimmed[head_sb] == 3      # cache + b + d
    eng.crash_and_recover()
    assert np.asarray(eng.astate.span_refs).tolist() == \
        refs_trimmed.tolist()
    assert int(ja.span_sbs(eng.acfg, int(
        eng.astate.sb_block_words[head_sb]))) == lease_sbs

    for lane in (b, c, d):
        eng.finish(lane)
    eng.drop_prefix_cache()                # last lease + record out
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 0
    assert int(np.asarray(eng.astate.span_refs).sum()) == 0
    assert ja.live_blocks(eng.astate, eng.acfg)[0] == 0
    assert eng.prefix_store.walk() == []


def test_engine_finished_lane_offset_poisoned(mesh):
    """Satellite regression (stale-offset hazard): once a lane finishes,
    its span records are poisoned — a span reallocated at the same
    offset can never be released through the dead lane, and a double
    ``finish`` raises instead of silently freeing someone else's span."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=2, max_seq=64,
                        pages_per_sb=4)
    rng = np.random.default_rng(7)
    prompt = [int(t) for t in rng.integers(1, cfg.vocab_size, size=40)]

    a = eng.add_request(prompt, share_prefix=True)
    off_a, n_a = eng.large_spans[a]
    for _ in range(len(prompt)):
        eng.step()
    eng.publish_prefix(a)                            # cache: prefix lease
    eng.finish(a)                                    # owner's lease drops
    # the dead lane's span records are gone the moment it finishes …
    assert a not in eng.large_spans and a not in eng.shared_spans
    refs_before = np.asarray(eng.astate.span_refs).copy()
    with pytest.raises(KeyError):
        eng.finish(a)                                # … and a second finish
    # raises without releasing anything through the dead lane
    assert np.array_equal(np.asarray(eng.astate.span_refs), refs_before)

    eng.drop_prefix_cache()                          # last lease → span dies
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 0
    b = eng.add_request(prompt)                      # best-fit: same offset
    off_b, n_b = eng.large_spans[b]
    assert off_b == off_a                            # the hazard setup
    head_sb = off_b // eng.acfg.sb_words
    # no transient record of the dead lane pins or can free the offset:
    # per-page refs never cover span pages, and the fresh span is owned
    # solely by b's new lease
    assert not (set(eng.page_refs) & set(range(off_b, off_b + n_b)))
    ext = ja.span_sbs(eng.acfg, n_b)
    assert np.asarray(eng.astate.span_refs)[
        head_sb:head_sb + ext].tolist() == [1] * ext
    # recovery recounts from live roots only — still nothing stale
    eng.crash_and_recover()
    assert not (set(eng.page_refs) & set(range(off_b, off_b + n_b)))
    assert int(eng.astate.sb_class[head_sb]) == ja.LARGE_CLS
    eng.finish(b)
    assert ja.live_blocks(eng.astate, eng.acfg)["large"] == 0


def test_prefix_hit_requires_exact_tokens(mesh):
    """Hash-keyed cache regression: a 48-bit key collision must never
    serve another prompt's KV — hits on entries published this process
    verify exact token equality (recovered entries, whose tokens died
    with the crash, match by hash alone — the documented residual)."""
    import dataclasses as dc
    from repro.core.prefix_index import hash_tokens
    cfg = dc.replace(get_smoke_config("qwen2_5_32b"), page_size=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=3, max_seq=64)
    prompt = [5, 9, 3, 7, 2, 8, 1, 4]
    a = eng.add_request(prompt)
    for _ in range(len(prompt)):
        eng.step()
    eng.publish_prefix(a)
    # forge a collision: alias the published entry under another
    # prompt's hash, exactly what equal 48-bit FNV digests would do
    other = [6, 6, 6, 6, 6, 6, 6, 6]
    eng._prefix_cache[hash_tokens(other)] = \
        eng._prefix_cache[hash_tokens(prompt)]
    eng._prefix_tokens[hash_tokens(other)] = tuple(prompt)
    b = eng.add_request(other, share_prefix=True)
    assert int(np.asarray(eng.dstate["pos"][b])) == 0   # miss, no KV reuse
    # the genuine prompt still hits
    c = eng.add_request(prompt, share_prefix=True)
    assert int(np.asarray(eng.dstate["pos"][c])) == len(prompt)
    for lane in (a, b, c):
        eng.finish(lane)
    del eng._prefix_cache[hash_tokens(other)]           # drop the forgery
    del eng._prefix_tokens[hash_tokens(other)]
    eng.drop_prefix_cache()


def test_prefix_sharing_refcounts(mesh):
    """RadixAttention-style prompt sharing over the paged allocator:
    shared pages are referenced by several block tables and return to the
    free pool only when the last reference drops — the paper's block-
    disjointness discipline extended with refcounts."""
    import dataclasses as dc
    from repro.core import jax_alloc as ja
    cfg = dc.replace(get_smoke_config("qwen2_5_32b"), page_size=4)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=4, max_seq=64)
    prompt = [5, 9, 3, 7, 2, 8, 1, 4]              # exactly 2 pages

    a = eng.add_request(prompt)
    for _ in range(len(prompt)):
        eng.step()
    eng.publish_prefix(a)
    pages_a = np.asarray(eng.dstate["block_table"][a])
    shared = set(pages_a[:2].tolist())

    # control: same prompt, no sharing
    c = eng.add_request(prompt)
    for _ in range(len(prompt)):
        eng.step()

    # shared-prefix request starts at pos = len(prompt) re-using pages
    b = eng.add_request(prompt, share_prefix=True)
    assert int(np.asarray(eng.dstate["pos"][b])) == len(prompt)
    pages_b = np.asarray(eng.dstate["block_table"][b])
    assert set(pages_b[:2].tolist()) == shared
    # both continue generating; teacher-forced outputs agree with control
    for _ in range(6):
        eng.step()
    assert eng.sessions[b].tokens[len(prompt):] == \
        eng.sessions[c].tokens[len(prompt):len(eng.sessions[b].tokens)]

    live0 = ja.live_blocks(eng.astate, eng.acfg)[0]
    eng.finish(a)                                   # shared pages survive
    assert set(np.asarray(eng.dstate["block_table"][b])[:2].tolist()) \
        == shared
    eng.finish(b)                                   # cache still holds them
    eng.finish(c)
    live1 = ja.live_blocks(eng.astate, eng.acfg)[0]
    assert live1 == 2                               # only the cached prefix
    eng.drop_prefix_cache()
    assert ja.live_blocks(eng.astate, eng.acfg)[0] == 0


def test_serve_crash_mid_generation_matches_reference():
    """The served entry point (``launch.serve.serve``, which
    ``chip_smoke.py`` runs at full width): a crash + recovery in the
    middle of generation changes no lane's tokens, on the span path and
    the lazy per-page path alike, and the allocator's occupancy matches
    the lanes' holdings after both runs."""
    from repro.launch import serve as S
    cfg = dataclasses.replace(get_smoke_config("starcoder2_3b"), page_size=8)
    params = S.init_params(cfg, 0)
    # 140 tokens = 18 pages > 16 per superblock: the decode-ahead span
    prompts = S.seeded_prompts(0, (140, 30, 17, 3), cfg.vocab_size)
    kw = dict(max_seq=256, gen=6)
    ref = S.serve(cfg, params, prompts, **kw)
    assert ref.span_requests == [0]
    assert ref.recovery is None
    assert all(len(t) == 6 for t in ref.tokens)
    last = max(len(p) - 1 + 6 for p in prompts)
    crashed = S.serve(cfg, params, prompts, crash_at=last - 3, **kw)
    assert crashed.recovery["live_before"] == crashed.recovery["live_after"]
    assert crashed.tokens == ref.tokens
    assert crashed.occupancy == ref.occupancy
    assert ref.occupancy["live_spans"] == 1
    assert ref.occupancy["live_pages"] == ref.occupancy["lane_pages"] > 0


def test_check_occupancy_catches_a_page_no_lane_holds(mesh):
    """A page the allocator hands out but no block table holds is a
    leak: the occupancy check must refuse it."""
    from repro.core import jax_alloc as ja
    cfg = dataclasses.replace(get_smoke_config("starcoder2_3b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=2, max_seq=64)
    eng.add_request([3, 1, 4, 1, 5, 9, 2, 6, 5])
    for _ in range(10):
        eng.step()
    occ = eng.check_occupancy()
    assert occ["live_pages"] == occ["lane_pages"] == 2
    need = np.zeros((2,), bool)
    need[0] = True
    eng.astate, _ = ja.alloc(eng.astate, eng.acfg, PAGE_CLS,
                             jnp.asarray(need))
    with pytest.raises(AssertionError, match="occupancy"):
        eng.check_occupancy()


# ---------------------------------------------------------------------------
# the engine's own spans and counters (repro.obs)
# ---------------------------------------------------------------------------
PROMPTS = ([3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4], [9] * 19)
MAX_NEW = (6, 3, 12)


class _OpenSpans:
    """An ``obs`` annotator that keeps the names of the open spans."""

    def __init__(self):
        self.stack = []

    def __call__(self, name):
        self.name = name
        return self

    def __enter__(self):
        self.stack.append(self.name)

    def __exit__(self, *exc):
        self.stack.pop()


class _ReadsOf:
    """``numpy`` for the engine module, recording the innermost open
    span at every ``asarray`` of a device array (a device->host read)."""

    def __init__(self, spans, reads):
        self._spans, self._reads = spans, reads

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, x, *a, **kw):
        if isinstance(x, jax.Array):
            self._reads.append(self._spans.stack[-1]
                               if self._spans.stack else None)
        return np.asarray(x, *a, **kw)


@pytest.fixture(scope="module")
def served(mesh):
    """Three requests served to the end through the scheduler on two
    lanes (page 8), with the engine's allocator calls and device->host
    reads recorded from the outside, and the registry's record of the
    run."""
    from repro import obs
    from repro.serving import engine as engine_mod
    from repro.serving.scheduler import Scheduler
    cfg = dataclasses.replace(get_smoke_config("starcoder2_3b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=2, max_seq=64)
    calls = {"alloc": 0}
    alloc = eng._alloc

    def counted(**kw):
        calls["alloc"] += 1
        return alloc(**kw)
    eng._alloc = counted
    sched = Scheduler(eng)
    spans, reads = _OpenSpans(), []
    obs.reset_all()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(obs.get_registry(), "annotator", spans)
        mp.setattr(engine_mod, "np", _ReadsOf(spans, reads))
        for p, n in zip(PROMPTS, MAX_NEW):
            sched.submit(p, max_new_tokens=n)
        results = sched.drain()
    snap = obs.snapshot()
    steps = [a for name, _, _, a in obs.span_events() if name == "engine.step"]
    return dict(eng=eng, calls=calls, results=results, reads=reads,
                steps=steps, phases=snap["phases"])


def test_engine_counts_prompt_and_sampled_lane_steps(served):
    steps = served["steps"]
    assert (sum(a["prefill_lanes"] for a in steps)
            == sum(len(p) - 1 for p in PROMPTS))
    assert sum(a["decode_lanes"] for a in steps) == sum(MAX_NEW)
    assert all(len(served["results"][i]) == len(p) + n
               for i, (p, n) in enumerate(zip(PROMPTS, MAX_NEW)))
    assert len(steps) == served["phases"]["engine.step"]["calls"]


def test_engine_page_alloc_span_per_boundary_allocation(served):
    ph = served["phases"]
    assert served["calls"]["alloc"] > 0
    assert ph["engine.page_alloc"]["calls"] == served["calls"]["alloc"]
    # each allocation reads the offsets it got and the block table
    assert ph["engine.d2h.offs"]["calls"] == served["calls"]["alloc"]


def test_engine_finish_timed_once_per_finish(served):
    ph = served["phases"]
    assert ph["engine.finish"]["calls"] == len(PROMPTS)
    assert ph["engine.finish"]["seconds"] > 0
    assert ph["engine.add_request"]["calls"] == len(PROMPTS)
    assert ph["sched.admit"]["calls"] == len(PROMPTS)
    assert ph["sched.step"]["calls"] == ph["engine.step"]["calls"]


def test_every_device_read_is_a_counted_span(served):
    """Each device->host read of the served path happens inside its own
    ``engine.d2h.*`` span, and each such span is one read."""
    reads = served["reads"]
    assert reads
    assert all(r is not None and r.startswith("engine.d2h.") for r in reads)
    assert len(reads) == sum(row["calls"]
                             for name, row in served["phases"].items()
                             if name.startswith("engine.d2h."))


@pytest.mark.parametrize("boundary", [False, True])
def test_engine_step_reads_the_device_a_documented_number_of_times(
        mesh, boundary):
    """A plain step reads ``pos`` and the tokens; a step where a lane
    crosses a page also reads the block table twice and the offsets the
    allocator returned."""
    from repro import obs
    cfg = dataclasses.replace(get_smoke_config("starcoder2_3b"), page_size=8)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, mesh, params, lanes=2, max_seq=64)
    eng.add_request([5, 9, 3])
    for _ in range(8 if boundary else 1):      # to pos 8, or to pos 1
        eng.step()
    obs.reset_all()
    eng.step()
    snap = obs.snapshot()
    want = {"engine.d2h.pos": 1, "engine.d2h.tokens": 1}
    if boundary:
        want.update({"engine.d2h.block_table": 2, "engine.d2h.offs": 1})
    assert {n: row["calls"] for n, row in snap["phases"].items()
            if n.startswith("engine.d2h.")} == want
    assert snap["phases"]["engine.step"]["calls"] == 1


@pytest.mark.parametrize("scope", ["kv_gather", "kv_write", "attention",
                                   "ffn", "logits"])
def test_decode_step_carries_its_named_scopes(served, scope):
    import re
    eng = served["eng"]
    text = eng.step_fn.lower(eng.params, eng.dstate,
                             jnp.asarray(eng.cur_tokens)).as_text(
                                 dialect="hlo", debug_info=True)
    assert re.search(rf'op_name="([^"]*/)?{scope}/', text)
