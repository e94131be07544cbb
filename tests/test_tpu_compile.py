"""Ahead-of-time compiles of the served path for a described TPU v5e.

Nothing runs: each program is lowered and compiled by the TPU compiler
for one chip of a ``v5e:2x2`` topology that is described, not attached.
That catches what interpret mode cannot — block shapes the TPU tiling
refuses, primitives Mosaic cannot lower, programs that do not fit the
chip's 16 GB — at no chip time.

The topology is described inside a fixture (never at import time): one
process at a time may load the TPU library, and every test worker
imports this file.  Keep these tests in this one file.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.mellum2_12b_a2_5b import ep4_config
from repro.core import jax_alloc as ja
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.kv_update.kernel import kv_update
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.models import transformer as T
from repro.serving import decode as dec
from repro.serving.engine import PAGE_CLS, arena_config, window_arena_config

V5E_HBM_BYTES = 16e9
LANES, MAX_SEQ = 8, 4096          # chip_smoke.py's engine


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # entries compiled for a described chip cannot be read back
        # without one: keep them out of the persistent cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:            # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def starcoder():
    return get_config("starcoder2_3b")


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile_decode_step(mesh, cfg, lanes, max_seq):
    """The served decode step compiled for the described chip, with the
    parameter and decode-state shapes it was compiled for (the engine's
    arenas: both pools where the configuration has window layers)."""
    acfg = arena_config(cfg, lanes, max_seq)
    wacfg = window_arena_config(cfg, lanes)
    pshape = jax.eval_shape(
        functools.partial(T.init_params, cfg, jax.random.PRNGKey(0)))
    dshape = jax.eval_shape(functools.partial(
        dec.make_dstate, cfg, batch=lanes, max_seq=max_seq,
        pages_per_shard=acfg.total_words + 1,
        ring_pages_per_shard=wacfg and wacfg.total_words + 1))
    step, pspecs, sspecs = dec.make_decode_step(cfg, mesh, pshape)

    def place(shapes, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            shapes, specs)

    tokens = jax.ShapeDtypeStruct((lanes,), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(place(pshape, pspecs), place(dshape, sspecs),
                          tokens).compile()
    return compiled, pshape, dshape


def test_decode_step_starcoder2_3b_fits_one_v5e(mesh, starcoder):
    compiled, pshape, _ = _compile_decode_step(mesh, starcoder, LANES,
                                               MAX_SEQ)
    ma = compiled.memory_analysis()
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(pshape))
    assert param_bytes > 6e9                       # bf16 at published widths
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM_BYTES


_MiB = 1 << 20


@pytest.mark.parametrize("arch,num_layers,max_seq,temp_limit", [
    ("starcoder2_3b", None, 4096, 64 * _MiB),
    # one 13-layer pipeline stage; 72 MiB of its temporaries is one
    # [6144, 6144] weight re-laid out in the layer loop, not K/V
    ("granite_20b", 13, 8192, 96 * _MiB),
], ids=["starcoder2-3b", "granite-20b-13l"])
def test_decode_step_updates_the_stacked_arena_in_place(
        mesh, arch, num_layers, max_seq, temp_limit):
    """The stacked K/V arena is carried through the layer loop and
    written where it lies: no temporary buffer holds a copy of it, and
    no op copies or rewrites it whole (16 lanes, the cells' sizes)."""
    cfg = get_config(arch)
    if num_layers:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    compiled, _, dshape = _compile_decode_step(mesh, cfg, 16, max_seq)
    arena = dshape["units"]["l0"]["k"]
    assert arena.shape[0] == cfg.full_units
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit
    whole = ",".join(map(str, arena.shape))
    moves = re.findall(rf"= \w+\[{whole}\]\S* (copy|dynamic-update-slice)\(",
                       compiled.as_text())
    assert not moves, moves


def test_decode_step_mellum2_ep4_fits_one_v5e(mesh):
    """Mellum2-12B-A2.5B's one-chip share (16 of 64 experts) at the
    benchmark's 16 lanes and max_seq 8192: the full layers' arena, the
    window layers' ring pool and the weights, plus the step's
    temporaries, fit the chip."""
    cfg = ep4_config()
    compiled, pshape, dshape = _compile_decode_step(mesh, cfg, 16, 8192)
    ma = compiled.memory_analysis()
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(pshape))
    assert 7.6e9 < param_bytes < 7.7e9           # 3.83B bf16 parameters
    # 21 window layers on 9-slot rings, 7 full layers on 64-page tables
    assert dshape["ring_table"].shape == (16, 9)
    assert dshape["block_table"].shape == (16, 64)
    assert dshape["units"]["l0"]["k"].shape[:2] == (7, 161)
    assert dshape["units"]["l3"]["k"].shape[:2] == (7, 1297)
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM_BYTES


# The parent's compile (before window layers had a pool of their own) of
# these steps, for a described v5e: argument, output and temporary bytes
_DENSE_STEP_BYTES = {"starcoder2-3b": (9147515904, 3087017472, 1032192),
                     "granite-20b-13l": (12170683392, 1105536512, 77084160)}


@pytest.mark.parametrize("arch,num_layers,max_seq", [
    ("starcoder2_3b", None, 4096), ("granite_20b", 13, 8192)],
    ids=["starcoder2-3b", "granite-20b-13l"])
def test_dense_decode_step_is_unchanged(mesh, arch, num_layers, max_seq):
    """A configuration without window layers keeps one pool: the same
    decode state and the same compiled step, byte for byte in size."""
    cfg = get_config(arch)
    if num_layers:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    compiled, _, dshape = _compile_decode_step(mesh, cfg, 16, max_seq)
    assert sorted(dshape) == ["block_table", "kv_pos", "pos", "tail",
                              "units"]
    assert dshape["block_table"].shape == (16, max_seq // 128)
    ma = compiled.memory_analysis()
    got = (ma.argument_size_in_bytes, ma.output_size_in_bytes,
           ma.temp_size_in_bytes)
    assert got == _DENSE_STEP_BYTES[
        "starcoder2-3b" if num_layers is None else "granite-20b-13l"]


@pytest.mark.parametrize("program", ["alloc", "free", "alloc_large",
                                     "trim_large"])
def test_allocator_program(program, one_chip, starcoder):
    acfg = arena_config(starcoder, LANES, MAX_SEQ)
    state = _on(one_chip, jax.eval_shape(
        functools.partial(ja.init_state, acfg, max_roots=LANES + 4)))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = arg((), jnp.int32)
    fn, kw = {
        "alloc": (functools.partial(ja.alloc, cfg=acfg, cls=PAGE_CLS),
                  dict(need=arg((LANES,), jnp.bool_))),
        "free": (functools.partial(ja.free, cfg=acfg, cls=PAGE_CLS),
                 dict(offs=arg((acfg.cache_cap,), jnp.int32),
                      mask=arg((acfg.cache_cap,), jnp.bool_))),
        "alloc_large": (functools.partial(ja.alloc_large, cfg=acfg),
                        dict(nwords=i32)),
        "trim_large": (functools.partial(ja.trim_large, cfg=acfg),
                       dict(off=i32, n_keep=i32, n_held=i32)),
    }[program]
    jax.jit(fn).lower(state=state, **kw).compile()


def _flash():
    q = ((1, 24, 2048, 128), jnp.bfloat16)
    kv = ((1, 2, 2048, 128), jnp.bfloat16)
    return flash_attention, (q, kv, kv)


def _arena_pages():
    return arena_config(get_config("starcoder2_3b"), LANES,
                        MAX_SEQ).total_words + 1


def _kv_update():
    pages = _arena_pages()
    arena = ((pages, 128, 2, 128), jnp.bfloat16)
    tok = ((LANES, 2, 128), jnp.bfloat16)
    idx = ((LANES,), jnp.int32)
    return kv_update, (arena, arena, tok, tok, idx, idx)


def _paged():
    pages = _arena_pages()
    arena = ((pages, 128, 2, 128), jnp.bfloat16)
    return paged_attention, (((LANES, 24, 128), jnp.bfloat16), arena, arena,
                             ((LANES, MAX_SEQ // 128), jnp.int32),
                             ((LANES,), jnp.int32))


def _ssd():
    # mamba2 widths: 32 heads of 64, state 128, 2048 steps
    return ssd_scan, (((1, 32, 2048, 64), jnp.float32),
                      ((1, 32, 2048), jnp.float32),
                      ((1, 2048, 128), jnp.float32),
                      ((1, 2048, 128), jnp.float32))


@pytest.mark.parametrize("kernel", [_flash, _kv_update, _paged, _ssd],
                         ids=["flash_attention", "kv_update",
                              "paged_attention", "ssd_scan"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, shapes = kernel()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
