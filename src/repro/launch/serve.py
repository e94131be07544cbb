"""Serving launcher: paged continuous-batching generation.

Full published widths (random weights drawn from ``--seed``):
  PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b

CPU-scale example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-32b --smoke \
      --max-seq 512 --prompt-lens 300,130,40,5 --gen 8 --crash-at 300

Requests are admitted staggered so that every lane emits its last
generated token on the same, final step: each lane's generation window
is the last ``--gen`` steps.  A prompt longer than one superblock of
pages takes the engine's decode-ahead span path; shorter prompts back
their pages lazily, one page per boundary crossing.

``--crash-at N`` drops all transient allocator state before step N and
recovers via the vectorized GC before continuing (the paper's
recoverability criterion, live).  After the last step the allocator's
live pages are checked against the pages the lanes hold, then every
lane is evicted and the arena must be empty.
"""

from __future__ import annotations

import argparse
import dataclasses
import statistics
import time

import jax
import numpy as np

from ..configs import get_config, get_smoke_config
from ..core import jax_alloc as ja
from ..models import transformer as T
from ..runtime import enable_compile_cache, make_host_mesh
from ..serving.engine import PAGE_CLS, ServingEngine

# one span-path prompt (> 16 pages of 128 tokens), four that cross page
# boundaries on the lazy path, three that fit one page
DEFAULT_PROMPT_LENS = (2300, 1000, 700, 260, 130, 40, 9, 1)


@dataclasses.dataclass
class ServeResult:
    """What one ``serve`` run emitted and measured."""
    tokens: list[list[int]]     # generated tokens per request (no prompt)
    span_requests: list[int]    # requests that took the decode-ahead span
    recovery: dict | None       # crash_and_recover stats (None: no crash)
    occupancy: dict             # check_occupancy before eviction
    first_step_s: float         # first decode step, compilation included
    step_s: list[float]         # every later step, block_until_ready'd

    @property
    def steady_step_s(self) -> float:
        return statistics.median(self.step_s)


def init_params(cfg, seed: int):
    """Random weights from ``seed``, built on the default device."""
    return jax.jit(T.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))


def seeded_prompts(seed: int, lengths, vocab_size: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab_size, size=n).tolist() for n in lengths]


def serve(cfg, params, prompts, *, max_seq: int, gen: int,
          crash_at: int = -1) -> ServeResult:
    """Serve ``prompts`` on one engine with a lane per request until each
    has ``gen`` generated tokens, crashing and recovering before step
    ``crash_at`` (< 0: no crash).  Request i is admitted at the step
    that makes it finish on the last one."""
    if max(len(p) for p in prompts) + gen >= max_seq - 1:
        raise ValueError("prompt + gen must stay below max_seq - 1")
    engine = ServingEngine(cfg, make_host_mesh(), params,
                           lanes=len(prompts), max_seq=max_seq)
    need = [len(p) - 1 + gen for p in prompts]   # steps from admission
    n_steps = max(need)
    admit: dict[int, list[int]] = {}
    for i, n in enumerate(need):
        admit.setdefault(n_steps - n, []).append(i)
    lane_of: dict[int, int] = {}
    recovery = None
    times = []
    for step in range(n_steps):
        for i in admit.get(step, []):
            lane_of[i] = engine.add_request(prompts[i])
        if step == crash_at:
            recovery = engine.crash_and_recover()
        t0 = time.perf_counter()
        engine.step()
        jax.block_until_ready(engine.dstate)
        times.append(time.perf_counter() - t0)
    tokens = [engine.sessions[lane_of[i]].tokens[len(p):]
              for i, p in enumerate(prompts)]
    if any(len(t) != gen for t in tokens):
        raise AssertionError(f"expected {gen} tokens per request, got "
                             f"{[len(t) for t in tokens]}")
    span_requests = [i for i in range(len(prompts))
                     if lane_of[i] in engine.large_spans]
    occupancy = engine.check_occupancy()
    for lane in list(engine.sessions):
        engine.finish(lane)
    live = ja.live_blocks(engine.astate, engine.acfg)
    if live[PAGE_CLS] or live["large"]:
        raise AssertionError(f"pages left after evicting every lane: {live}")
    return ServeResult(tokens=tokens, span_requests=span_requests,
                       recovery=recovery, occupancy=occupancy,
                       first_step_s=times[0], step_s=times[1:])


def device_line() -> str:
    devs = jax.devices()
    return (f"platform={devs[0].platform} kind={devs[0].device_kind} "
            f"count={len(devs)}")


def main(argv=None) -> ServeResult:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's reduced smoke widths")
    ap.add_argument("--seed", type=int, default=0,
                    help="draws the weights and the prompt tokens")
    ap.add_argument("--max-seq", type=int, default=4096)
    ap.add_argument("--prompt-lens", default=",".join(
        map(str, DEFAULT_PROMPT_LENS)),
        help="one request, and one lane, per length")
    ap.add_argument("--gen", type=int, default=32,
                    help="generated tokens per request")
    ap.add_argument("--crash-at", type=int, default=-1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    print(f"[serve] device {device_line()}", flush=True)
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    params = init_params(cfg, args.seed)
    lengths = [int(n) for n in args.prompt_lens.split(",")]
    prompts = seeded_prompts(args.seed, lengths, cfg.vocab_size)
    res = serve(cfg, params, prompts, max_seq=args.max_seq, gen=args.gen,
                crash_at=args.crash_at)
    if res.recovery is not None:
        print(f"[serve] crash at step {args.crash_at}; recovery: "
              f"{res.recovery}")
    print(f"[serve] first step {res.first_step_s} s, steady "
          f"{res.steady_step_s} s/step; occupancy {res.occupancy}")
    for i, toks in enumerate(res.tokens):
        path = "span" if i in res.span_requests else "lazy"
        print(f"request {i} ({len(prompts[i])}-token prompt, {path}): "
              f"{len(toks)} tokens: {toks[:16]}")
    return res


if __name__ == "__main__":
    main()
