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

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import jax_alloc as ja
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.kv_update.kernel import kv_update
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.models import transformer as T
from repro.serving import decode as dec
from repro.serving.engine import PAGE_CLS, arena_config

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


def test_decode_step_starcoder2_3b_fits_one_v5e(mesh, starcoder):
    cfg = starcoder
    acfg = arena_config(cfg, LANES, MAX_SEQ)
    pshape = jax.eval_shape(
        functools.partial(T.init_params, cfg, jax.random.PRNGKey(0)))
    dshape = jax.eval_shape(functools.partial(
        dec.make_dstate, cfg, batch=LANES, max_seq=MAX_SEQ,
        pages_per_shard=acfg.total_words + 1))
    step, pspecs, sspecs = dec.make_decode_step(cfg, mesh, pshape)

    def place(shapes, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, s)),
            shapes, specs)

    tokens = jax.ShapeDtypeStruct((LANES,), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    compiled = step.lower(place(pshape, pspecs), place(dshape, sspecs),
                          tokens).compile()
    ma = compiled.memory_analysis()
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(pshape))
    assert param_bytes > 6e9                       # bf16 at published widths
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < V5E_HBM_BYTES


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
