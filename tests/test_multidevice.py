"""Numeric parity of the sharded paths on a real multi-device mesh.

Runs in a subprocess with ``--xla_force_host_platform_device_count=4``
(the flag must precede jax init, so it cannot run in the main pytest
process): a (data=2, model=2) mesh exercises

  * shard_map decode: row/col-parallel TP, slot-sharded paged KV,
    distributed-softmax merge, vocab-parallel sampling — vs the
    single-device oracle;
  * pjit train_step with the FSDP×TP sharding rules — vs 1-device.

This is the strongest correctness evidence for the distribution layer:
the 512-device dry-run proves it compiles; this proves it computes the
same numbers.
"""

import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    import sys
    sys.path.insert(0, "src")
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving import decode as dec
    from repro.distributed import sharding as shrules
    from repro.runtime import make_mesh, named_sharding
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.step import make_train_step
    from jax.sharding import PartitionSpec as P

    mesh1 = make_mesh((1, 1), ("data", "model"),
                      devices=jax.devices()[:1])
    mesh4 = make_mesh((2, 2), ("data", "model"))

    # smoke config with dims divisible by tp=2 everywhere
    cfg = dataclasses.replace(get_smoke_config("qwen2_5_32b"),
                              dtype=jnp.float32, vocab_size=128)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    B, S = 4, 12
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab_size)

    # ---- decode parity: mesh (1,1) vs (2,2) --------------------------------
    def run_decode(mesh):
        pshape = jax.eval_shape(lambda: params)
        step, pspecs, sspecs = dec.make_decode_step(cfg, mesh, pshape,
                                                    return_logits=True)
        ds = dec.make_dstate(cfg, batch=B, max_seq=32,
                             dp_shards=mesh.shape["data"])
        Pn = ds["block_table"].shape[1]
        pages_per_shard = ds["units"]["l0"]["k"].shape[1] // \
            mesh.shape["data"]
        # shard-local page ids: each data shard's sequences use its pool
        bt = np.zeros((B, Pn), np.int32)
        per_shard = B // mesh.shape["data"]
        for b in range(B):
            lane_in_shard = b % per_shard
            bt[b] = lane_in_shard * Pn + np.arange(Pn)
        ds["block_table"] = jnp.asarray(bt)
        outs = []
        for t in range(S):
            ds, tok, lg = step(params, ds, toks[:, t])
            outs.append(np.asarray(lg))
        return np.stack(outs, 1)

    l1 = run_decode(mesh1)
    l4 = run_decode(mesh4)
    err = np.abs(l1 - l4).max() / (np.abs(l1).max() + 1e-9)
    assert err < 1e-4, f"decode mesh parity: rel={err:.3e}"
    print(f"DECODE-PARITY-OK rel={err:.2e}")

    # ---- sequence-parallel decode (batch < dp — the long_500k path) --------
    # hybrid smoke arch: RG-LRU state + windowed attention, batch 1
    cfgh = dataclasses.replace(get_smoke_config("recurrentgemma_9b"),
                               dtype=jnp.float32, vocab_size=128,
                               page_size=4, window=8)
    paramsh = T.init_params(cfgh, jax.random.PRNGKey(2))
    tok1 = jax.random.randint(jax.random.PRNGKey(3), (1, 10), 0, 128)
    lfull, _ = T.forward(cfgh, paramsh, {"tokens": tok1})

    def run_seqpar(mesh):
        dp = mesh.shape["data"]
        pshape = jax.eval_shape(lambda: paramsh)
        step, _, _ = dec.make_decode_step(cfgh, mesh, pshape,
                                          batch_sharded=False,
                                          return_logits=True)
        ds = dec.make_dstate(cfgh, batch=1, max_seq=16, dp_shards=dp)
        # window layers read the ring table: ring slot j lives on data
        # shard j // (R/dp); ids are shard-local
        R = ds["ring_table"].shape[1]
        bt = (np.arange(R, dtype=np.int32) % (R // dp))[None, :]
        ds["ring_table"] = jnp.asarray(bt)
        outs = []
        for t in range(10):
            ds, tok, lg = step(paramsh, ds, tok1[:, t])
            outs.append(np.asarray(lg))
        return np.stack(outs, 1)

    s1 = run_seqpar(mesh1)
    s4 = run_seqpar(mesh4)
    err_sp = np.abs(s1 - s4).max() / (np.abs(s1).max() + 1e-9)
    assert err_sp < 1e-4, f"seq-parallel mesh parity: rel={err_sp:.3e}"
    err_or = np.abs(s4 - np.asarray(lfull)).max() / \
        (np.abs(np.asarray(lfull)).max() + 1e-9)
    assert err_or < 1e-3, f"seq-parallel vs oracle: rel={err_or:.3e}"
    print(f"SEQPAR-PARITY-OK rel={err_sp:.2e} oracle={err_or:.2e}")

    # ---- train-step parity: pjit on (2,2) vs single device -----------------
    step_fn = make_train_step(cfg, AdamWConfig(warmup_steps=1))
    batch = {"tokens": toks, "labels": toks}
    opt = init_opt_state(params)
    p1, o1, m1 = jax.jit(step_fn)(params, opt, batch)

    pspecs = shrules.train_param_specs(jax.eval_shape(lambda: params), mesh4)
    psh = jax.tree.map(lambda s: named_sharding(mesh4, s), pspecs)
    params4 = jax.tree.map(lambda a, s: jax.device_put(a, s), params, psh)
    osh = {"m": psh, "v": psh, "step": named_sharding(mesh4, P())}
    opt4 = {"m": jax.tree.map(lambda a, s: jax.device_put(a, s),
                              opt["m"], psh),
            "v": jax.tree.map(lambda a, s: jax.device_put(a, s),
                              opt["v"], psh),
            "step": opt["step"]}
    bsh = named_sharding(mesh4, P(("data",)))
    batch4 = jax.tree.map(lambda a: jax.device_put(a, bsh), batch)
    step4 = make_train_step(cfg, AdamWConfig(warmup_steps=1), mesh=mesh4)
    p4, o4, m4 = jax.jit(step4)(params4, opt4, batch4)
    dl = abs(float(m1["loss"]) - float(m4["loss"]))
    assert dl < 1e-4, f"loss mismatch {dl}"
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        d = np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max()
        assert d < 5e-4, f"param divergence {d}"
    print(f"TRAIN-PARITY-OK dloss={dl:.2e}")
""")


@pytest.mark.slow
def test_sharded_paths_match_single_device():
    res = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=900,
                         cwd=".")
    assert "DECODE-PARITY-OK" in res.stdout, res.stdout + res.stderr
    assert "SEQPAR-PARITY-OK" in res.stdout, res.stdout + res.stderr
    assert "TRAIN-PARITY-OK" in res.stdout, res.stdout + res.stderr
