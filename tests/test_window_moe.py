"""Window layers on the ring page pool, YaRN on the full layers, and an
expert layer that holds a share of the routed experts: Mellum2-12B-A2.5B
and recurrentgemma at smoke size, served through ``ServingEngine`` (page
4, window 8: a ring of 3 slots, which wraps every 12 positions)."""

import dataclasses
import importlib.util
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_smoke_config
from repro.core import jax_alloc as ja
from repro.launch.serve import init_params
from repro.layers.rope import layer_rope, yarn_freqs
from repro.models import transformer as T
from repro.runtime import make_host_mesh, shard_map
from repro.serving import decode as dec
from repro.serving import tp_layers as tpl
from repro.serving.engine import PAGE_CLS, ServingEngine

PAGE, WINDOW = 4, 8
RING = PAGE * 3                     # positions one trip round the ring


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh()


@pytest.fixture(scope="module")
def reference():
    """The benchmark's plain float32 Mellum2 reference (it imports
    nothing of the program)."""
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "configs" / "mellum2.py")
    spec = importlib.util.spec_from_file_location("mellum2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mellum(dtype=jnp.float32):
    return dataclasses.replace(get_smoke_config("mellum2_12b_a2_5b"),
                               page_size=PAGE, window=WINDOW, dtype=dtype)


def _model_dict(cfg) -> dict:
    """The configuration as a benchmark configuration file's ``model``."""
    m = {k: v for k, v in dataclasses.asdict(cfg).items() if k != "name"}
    m["dtype"] = jnp.dtype(cfg.dtype).name
    m["pattern"] = [list(p) for p in cfg.pattern]
    return m


def _logging_engine(cfg, mesh, params, lanes, max_seq):
    """A ServingEngine whose decode step also records, per step, every
    lane's position and logits."""
    eng = ServingEngine(cfg, mesh, params, lanes=lanes, max_seq=max_seq,
                        pages_per_sb=4)
    pshape = jax.eval_shape(lambda: params)
    step, _, _ = dec.make_decode_step(cfg, mesh, pshape, return_logits=True)
    log = []

    def logged(p, ds, toks):
        pos = np.asarray(ds["pos"])
        ds, tok, lg = step(p, ds, toks)
        log.append((pos, np.asarray(lg)))
        return ds, tok
    eng.step_fn = logged
    return eng, log


def _served_logits(log, lane, n):
    """The logits the engine computed for ``lane`` at positions 0..n-1."""
    out = {}
    for pos, lg in log:
        if pos[lane] < n:
            out[int(pos[lane])] = lg[lane]
    return np.stack([out[p] for p in range(n)])


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n).tolist()
            for n in lengths]


def test_mellum_served_matches_reference_past_ring_wraps(mesh, reference):
    """Served through ServingEngine, every position's logits agree with
    the plain float32 reference past three trips round the ring.  Both
    sides compute in float32 (the reference at HIGHEST precision); what
    is left is summation order, about 1e-6 of logits of order 1, so
    1e-4 holds with room.  A window layer that reads a page the ring has
    overwritten, or misses one, moves logits by 0.1 or more."""
    cfg = _mellum()
    assert cfg.ring_pages == 3
    seed = 7
    params = init_params(cfg, seed)
    eng, log = _logging_engine(cfg, mesh, params, lanes=3, max_seq=64)
    lanes = [eng.add_request(p) for p in _prompts(cfg, (5, 13))]
    for _ in range(3 * RING + 10):
        eng.step()
    model = _model_dict(cfg)
    for lane in lanes:
        toks = eng.sessions[lane].tokens
        assert len(toks) > 3 * RING + 1
        want = reference.reference_logits(model, seed, toks)
        got = _served_logits(log, lane, len(toks) - 1)
        np.testing.assert_allclose(got, want[:len(toks) - 1], rtol=0,
                                   atol=1e-4)
    # each lane holds all of its ring and one full-pool page per page
    occ = eng.check_occupancy()
    assert occ["lane_window_pages"] == 2 * cfg.ring_pages
    pos = np.asarray(eng.dstate["pos"])
    assert occ["live_pages"] == sum(-(-int(pos[lane]) // PAGE)
                                    for lane in lanes)


def test_recurrentgemma_decodes_past_its_window(mesh):
    """recurrentgemma's local attention, served through ServingEngine on
    its ring, matches ``models/transformer.forward`` past its window
    (float32; the tolerance of the decode-vs-oracle tests, whose bound
    is the recurrent scan's order of summation)."""
    cfg = dataclasses.replace(get_smoke_config("recurrentgemma_9b"),
                              page_size=PAGE, window=WINDOW,
                              dtype=jnp.float32, num_layers=6)
    assert cfg.ring_pages == 3 and not cfg.has_full_attn
    params = init_params(cfg, 3)
    eng, log = _logging_engine(cfg, mesh, params, lanes=2, max_seq=64)
    lane = eng.add_request(_prompts(cfg, (6,))[0])
    for _ in range(3 * RING + 6):
        eng.step()
    toks = eng.sessions[lane].tokens
    want, _ = T.forward(cfg, params, {"tokens": jnp.asarray([toks])})
    want = np.asarray(want[0, :len(toks) - 1])
    got = _served_logits(log, lane, len(toks) - 1)
    rel = np.abs(got - want).max() / np.abs(want).max()
    assert rel < 1e-3, rel


def _moe(cfg, p, x, mesh):
    fn = shard_map(lambda p, x: tpl.moe_decode_tp(cfg, p, x, "model"),
                   mesh=mesh, in_specs=(P(), P()), out_specs=P())
    return jax.jit(fn)(p, x)


def test_expert_shares_sum_to_uncut_layer(mesh):
    """Four chips' shares of an expert layer, each told which two of the
    eight routed experts it holds and routing over all eight, add up to
    the uncut layer's output."""
    whole = dataclasses.replace(_mellum(), num_experts=8, router_experts=0,
                                top_k=3)
    p = T._init_layer(whole, ("attn", "moe"), jax.random.PRNGKey(4))["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (6, whole.d_model),
                          jnp.float32)
    want = _moe(whole, p, x, mesh)
    parts = []
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, num_experts=2, router_experts=8,
                                    expert_first=first)
        ps = {k: (v if k == "router" else v[first:first + 2])
              for k, v in p.items()}
        parts.append(_moe(share, ps, x, mesh))
    np.testing.assert_allclose(sum(parts), want, rtol=1e-5, atol=1e-5)
    # each share adds something: every share's experts get tokens
    assert all(float(jnp.abs(y).max()) > 0 for y in parts)


def test_yarn_frequencies_and_factor_follow_the_formula():
    """Mellum2's full layers: YaRN's inverse frequencies (arXiv:2309.00071;
    HF ``_compute_yarn_parameters``) and its cos/sin factor; the window
    layers keep plain RoPE."""
    dh, theta, factor, orig = 128, 500000.0, 16.0, 8192
    got = yarn_freqs(dh, theta, factor, orig, 32.0, 1.0)

    def corr(rot):
        return dh * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low, high = math.floor(corr(32.0)), math.ceil(corr(1.0))
    assert (low, high) == (18, 35)
    for i in range(dh // 2):
        base = theta ** (-2 * i / dh)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want = base * (1 - ramp) + base / factor * ramp
        assert got[i] == pytest.approx(want, rel=1e-5), i
    assert got[0] == 1.0 and got[-1] == pytest.approx(
        theta ** (-(dh - 2) / dh) / factor, rel=1e-5)
    cfg = get_smoke_config("mellum2_12b_a2_5b")
    full, local = layer_rope(cfg, local=False), layer_rope(cfg, local=True)
    assert full["scale"] == pytest.approx(0.1 * math.log(16) + 1)
    assert local == {"theta": 500000.0}


def test_both_pools_balance_after_finish_and_recovery(mesh):
    """With lanes past the window, a crash drops both pools' transient
    state; recovery rebuilds both, no page leaks or is held twice, the
    lanes decode on exactly as an engine that did not crash, and after
    every lane finishes both pools are empty."""
    cfg = _mellum(jnp.bfloat16)
    params = init_params(cfg, 11)
    prompts = _prompts(cfg, (3, 9, 14), seed=1)

    def serve(crash):
        eng = ServingEngine(cfg, mesh, params, lanes=4, max_seq=96,
                            pages_per_sb=4)
        lanes = [eng.add_request(p) for p in prompts]
        for _ in range(2 * RING):
            eng.step()
        eng.finish(lanes[0])                 # its ring pages go back
        occ = eng.check_occupancy()
        assert occ["lane_window_pages"] == 2 * cfg.ring_pages
        stats = None
        if crash:
            stats = eng.crash_and_recover()
            assert stats["window_marked"] == 2 * cfg.ring_pages
            assert eng.check_occupancy() == occ
        lanes.append(eng.add_request(prompts[0]))
        for _ in range(RING + 5):
            eng.step()
        eng.check_occupancy()
        toks = [list(eng.sessions[lane].tokens) for lane in lanes[1:]]
        for lane in lanes[1:]:
            eng.finish(lane)
        for st, acfg in ((eng.astate, eng.acfg), (eng.wastate, eng.wacfg)):
            live = ja.live_blocks(st, acfg)
            assert live[PAGE_CLS] == 0 and live["large"] == 0
        return toks, stats

    plain, _ = serve(crash=False)
    crashed, stats = serve(crash=True)
    assert stats["phases"]["window_mark_sweep"]["items"] == \
        2 * cfg.ring_pages
    assert crashed == plain


def test_prefix_sharing_is_refused_with_window_layers(mesh):
    """A prefix hit would need the ring's last window of K/V: a
    configuration with window layers refuses to publish or look up a
    prefix, and the refusal takes no lane."""
    cfg = _mellum(jnp.bfloat16)
    eng = ServingEngine(cfg, mesh, init_params(cfg, 0), lanes=2, max_seq=64,
                        pages_per_sb=4)
    prompt = _prompts(cfg, (10,))[0]
    with pytest.raises(ValueError, match="window layers"):
        eng.add_request(prompt, share_prefix=True)
    assert sorted(eng.free_lanes) == [0, 1] and not eng.sessions
    lane = eng.add_request(prompt)
    for _ in range(10):
        eng.step()
    with pytest.raises(ValueError, match="window layers"):
        eng.publish_prefix(lane)
    assert not eng.prefix_cache.entries and eng.pending_publishes == 0
