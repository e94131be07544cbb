#!/usr/bin/env python3
"""Chip smoke test: serve starcoder2-3b at published widths on one TPU.

    python3 chip_smoke.py

One process, one chip, no arguments.  Random bf16 weights and prompts
are drawn from a fixed seed.  The served path is ``repro.launch.serve``,
the same function ``python -m repro.launch.serve`` runs:

  1. a reference run: 8 lanes, ``max_seq`` 4096, the engine's own arena
     sizing, 8 requests admitted staggered, 32 generated tokens each —
     one prompt longer than a superblock of pages (the decode-ahead span
     path), several that cross page boundaries on the lazy per-page path;
  2. the same requests on a second engine with the same weights, crashed
     (all transient allocator state dropped) and recovered in the middle
     of generation.

Every lane must emit exactly the reference tokens, and after each run
the allocator's live pages must equal the pages the lanes hold.  Any
failed check raises.  Without a TPU the script exits non-zero at once.
The last line of standard output is the one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.

JAX's persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<repo>/.jax_cache``.
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "starcoder2-3b"
PUBLISHED = dict(num_layers=30, d_model=3072, num_heads=24, num_kv_heads=2,
                 head_dim=128, d_ff=12288, vocab_size=49152)
MAX_SEQ, GEN, SEED = 4096, 32, 0


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def report(name: str, res, prompts) -> None:
    log(f"{name}: compile (first step) {res.first_step_s} s, steady "
        f"{res.steady_step_s} s/step (median of {len(res.step_s)}), "
        f"mean {sum(res.step_s) / len(res.step_s)} s/step")
    for i, toks in enumerate(res.tokens):
        path = "span" if i in res.span_requests else "lazy"
        log(f"{name}: request {i} ({len(prompts[i])}-token prompt, {path}) "
            f"{len(toks)} tokens: {toks}")
    log(f"{name}: occupancy {res.occupancy}")


def run(cfg, *, max_seq: int, gen: int, prompt_lens, seed: int) -> None:
    """Reference run, crashed run, and every check between them."""
    import jax
    from repro.launch import serve as S

    params = S.init_params(cfg, seed)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"config {cfg.name}: {n_params} params ({cfg.param_count()} in "
        f"matrices), {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads}/{cfg.num_kv_heads} heads, vocab {cfg.vocab_size},"
        f" {jax.numpy.dtype(cfg.dtype).name}")
    prompts = S.seeded_prompts(seed, prompt_lens, cfg.vocab_size)
    kw = dict(max_seq=max_seq, gen=gen)

    ref = S.serve(cfg, params, prompts, **kw)
    report("reference", ref, prompts)
    if not ref.span_requests:
        raise AssertionError("no request took the decode-ahead span path")
    lazy_crossing = [i for i, p in enumerate(prompts)
                     if i not in ref.span_requests and len(p) > cfg.page_size]
    if len(lazy_crossing) < 2:
        raise AssertionError(f"lazy page-crossing requests: {lazy_crossing}")

    crash_at = max(len(p) - 1 + gen for p in prompts) - gen // 2
    crashed = S.serve(cfg, params, prompts, crash_at=crash_at, **kw)
    report("crashed", crashed, prompts)
    log(f"crashed: crash before step {crash_at}, recovery "
        f"{json.dumps(crashed.recovery)}")
    rec = crashed.recovery
    if rec["live_before"] != rec["live_after"]:
        raise AssertionError(f"recovery changed the live pages: {rec}")
    for i, (a, b) in enumerate(zip(ref.tokens, crashed.tokens)):
        if a != b:
            raise AssertionError(f"request {i}: crashed run emitted {b}, "
                                 f"reference {a}")
    log(f"all {len(prompts)} requests emitted identical tokens with and "
        f"without the crash")


def main() -> int:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{devs[0].platform}); not running on it", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.launch.serve import DEFAULT_PROMPT_LENS, device_line
    from repro.runtime import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    log(f"device {device_line()}")
    cfg = get_config(ARCH)
    for k, v in PUBLISHED.items():
        if getattr(cfg, k) != v:
            raise AssertionError(f"{ARCH}.{k} = {getattr(cfg, k)}, "
                                 f"published {v}")
    if jax.numpy.dtype(cfg.dtype) != jax.numpy.bfloat16:
        raise AssertionError(f"{ARCH} dtype {cfg.dtype}, expected bf16")
    log(f"{len(DEFAULT_PROMPT_LENS)} lanes, one per request")
    run(cfg, max_seq=MAX_SEQ, gen=GEN, prompt_lens=DEFAULT_PROMPT_LENS,
        seed=SEED)
    log(f"peak_bytes_in_use {devs[0].memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
