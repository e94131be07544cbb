import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import/initialization (device count locks on init).

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

A CPU-only tool: it forces 512 virtual host devices before JAX starts
and compiles for them, so it never runs on (or needs) an accelerator.

For each cell this produces:
  * proof of compilation on the production mesh (256-chip single pod and
    512-chip two-pod);
  * ``memory_analysis()`` (fits-per-device evidence);
  * ``cost_analysis()`` raw numbers plus loop-corrected FLOPs/bytes and
    per-collective bytes from ``hlo_analysis`` (the §Roofline inputs).

Usage:
  python -m repro.launch.dryrun --arch granite-20b --shape train_4k
  python -m repro.launch.dryrun --arch all                 # every cell
  python -m repro.launch.dryrun ... --multi-pod            # 2×16×16 mesh
"""

import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from ..configs import ARCHS, SHAPES, applicable_shapes, canon, get_config
from ..launch import hlo_analysis, specs
from ..launch.mesh import make_production_mesh
from ..models import transformer as T
from ..serving import decode as dec
from ..train.optimizer import AdamWConfig
from ..train.step import make_train_step


def _analysis(lowered, compiled, mesh, extra):
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    chips = mesh.devices.size
    roof = hlo_analysis.analyze(compiled.as_text(), chips)
    out = {
        "cost_analysis_flops": float(ca.get("flops", 0.0)),
        "cost_analysis_bytes": float(ca.get("bytes accessed", 0.0)),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_bytes_estimate": ma.argument_size_in_bytes
            + ma.temp_size_in_bytes,
        },
        "roofline": roof,
    }
    out.update(extra)
    return out


def _apply_overrides(cfg, overrides: str):
    import dataclasses
    if not overrides:
        return cfg
    kw = {}
    for item in overrides.split(","):
        k, v = item.split("=")
        cur = getattr(cfg, k)
        kw[k] = type(cur)(v) if not isinstance(cur, bool) else v == "True"
    return dataclasses.replace(cfg, **kw)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: str = "") -> dict:
    cfg = _apply_overrides(get_config(arch), overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = SHAPES[shape_name]["kind"]
    info = dict(SHAPES[shape_name])
    scale = int(os.environ.get("REPRO_BATCH_SCALE", "1"))
    if scale != 1:
        info["global_batch"] *= scale
        SHAPES[shape_name] = info          # seen by specs builders
    t0 = time.time()

    if kind == "train":
        params = specs.abstract_params(cfg, mesh, "train")
        opt = specs.abstract_opt_state(params, mesh)
        batch = specs.train_batch_specs(cfg, shape_name, mesh)
        step = make_train_step(cfg, AdamWConfig(), mesh=mesh)
        shardings = jax.tree.map(lambda s: s.sharding, (params, opt, batch))
        jitted = jax.jit(step, in_shardings=shardings,
                         out_shardings=(shardings[0], shardings[1], None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params, opt, batch)
    elif kind == "prefill":
        params = specs.abstract_params(cfg, mesh, "serve")
        batch = specs.prefill_batch_specs(cfg, shape_name, mesh)

        from ..distributed.sharding import make_batch_constrainer
        constrain = make_batch_constrainer(mesh)

        def prefill(params, batch):
            logits, aux, kv = T.forward(cfg, params, batch, collect_kv=True,
                                        constrain=constrain)
            return logits[:, -1], kv

        shardings = jax.tree.map(lambda s: s.sharding, (params, batch))
        jitted = jax.jit(prefill, in_shardings=shardings)
        lowered = jitted.lower(params, batch)
    else:  # decode
        params = specs.abstract_params(cfg, mesh, "serve")
        dstate, tokens, batch_sharded = specs.decode_state_specs(
            cfg, shape_name, mesh)
        pshape = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), params)
        step, _, _ = dec.make_decode_step(cfg, mesh, pshape,
                                          batch_sharded=batch_sharded)
        lowered = step.lower(params, dstate, tokens)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    if os.environ.get("REPRO_SAVE_HLO"):
        import gzip
        hdir = pathlib.Path(os.environ["REPRO_SAVE_HLO"])
        hdir.mkdir(parents=True, exist_ok=True)
        name = (f"{canon(arch)}__{shape_name}__"
                f"{'2x16x16' if multi_pod else '16x16'}.hlo.gz")
        with gzip.open(hdir / name, "wt") as fh:
            fh.write(compiled.as_text())

    ntok = info["global_batch"] * (info["seq_len"] if kind != "decode" else 1)
    model_flops = 6 * cfg.active_param_count() * ntok
    if kind == "train":
        pass                               # 6ND already counts fwd+bwd
    else:
        model_flops = model_flops // 3     # 2ND forward-only
    extra = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "model_flops_global": float(model_flops),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    return _analysis(lowered, compiled, mesh, extra)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--override", default="", help="cfg overrides k=v,...")
    ap.add_argument("--tag", default="", help="suffix for perf variants")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [canon(args.arch)]
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        shapes = (applicable_shapes(arch) if args.shape == "all"
                  else [args.shape])
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                if args.tag:
                    tag += f"__{args.tag}"
                if args.skip_existing and (outdir / f"{tag}.json").exists():
                    print(f"SKIP {tag}", flush=True)
                    continue
                try:
                    res = run_cell(arch, shape, mp, args.override)
                    (outdir / f"{tag}.json").write_text(
                        json.dumps(res, indent=1, default=float))
                    r = res["roofline"]
                    print(f"OK   {tag}: compile={res['compile_s']}s "
                          f"dom={r['dominant']} "
                          f"t=({r['t_compute_s']:.4f},"
                          f"{r['t_memory_s']:.4f},"
                          f"{r['t_collective_s']:.4f})s "
                          f"mem={res['memory']['peak_bytes_estimate']/2**30:.1f}GiB/dev",
                          flush=True)
                except Exception as e:
                    failures += 1
                    print(f"FAIL {tag}: {e}", flush=True)
                    traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
