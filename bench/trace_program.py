#!/usr/bin/env python3
"""One run of ``bench/run.py``, with what its trace reduction leaves out.

    python3 bench/trace_program.py [--out FILE] [--obs 0|1] -- \\
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ``run.py``'s ``main`` with the arguments after ``--`` and prints
what it prints.  ``--obs 0`` turns the program's own metrics and spans
(``repro.obs``) off for the run.  ``--out`` gets one JSON object: the
end-to-end metrics of the run (a traced run's result line carries only
per-layer ones), and with ``--trace 1``, from the trace file before the
harness deletes it:

* ``idle``: the device's idle gaps labelled by the innermost program
  span (``engine.*``, ``sched.*``, ``engine_recovery.*``), and how the
  idle the harness labels ``ServingEngine.step`` splits among them;
* ``decode_scopes``: the decode program's op self time by named scope
  (``attention``, ``kv_write``, ``kv_gather``, ``ffn``, ``logits``,
  ``unscoped``), from the compiled program's op metadata;
* ``top_ops_scoped``: the harness's ten largest ops with their scope.
"""

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]

import run  # noqa: E402  (first: it starts the set-up clock)


def main(argv) -> int:
    split = argv.index("--") if "--" in argv else len(argv)
    own, rest = argv[:split], argv[split + 1:]
    out_path = own[own.index("--out") + 1] if "--out" in own else None
    obs_on = (own[own.index("--obs") + 1] if "--obs" in own else "1") != "0"

    import harness
    import program_trace as pt
    import reduce_trace as tr
    from repro import obs
    if not obs_on:
        obs.disable()
    extra: dict = {"obs_enabled": obs_on}

    build = harness.build

    def capture_build(*a, **k):
        params, engine, sched = build(*a, **k)
        extra["engine"] = (engine, engine.step_fn)   # before the wrappers
        return params, engine, sched

    reduce_file = tr.reduce_file

    def reduce_more(path, spans_of_interest=None):
        red = reduce_file(path, spans_of_interest)
        import jax
        import jax.numpy as jnp
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(str(path))
        planes = list(profile.planes)       # a view that reads once
        extra["idle"] = pt.idle_by_program_span(red, pt.host_spans(planes))
        # let go of the engine: the reference runs once it is freed
        engine, step_fn = extra.pop("engine")
        # compiled afresh: the program that ran may have come from the
        # compile cache, whose key leaves out op metadata by default, so
        # it may carry the scopes of another tree's source
        flag = "jax_compilation_cache_include_metadata_in_key"
        jax.clear_caches()
        jax.config.update(flag, True)
        try:
            hlo = step_fn.lower(engine.params, engine.dstate,
                                jnp.asarray(engine.cur_tokens)).compile()
        finally:
            jax.config.update(flag, False)
        scope_of = pt.hlo_op_scopes(hlo.as_text())
        module = "jit_" + step_fn.__name__
        extra["decode_scopes"] = pt.scope_times(planes, module, scope_of)
        extra["top_ops_scoped"] = [[n, s, scope_of.get(n)]
                                   for n, s in red["top_ops"]]
        return red

    run_cell = harness.run_cell

    def run_and_keep(cell, config, mix, **kw):
        out = run_cell(cell, config, mix, **kw)
        rec, setup_s = out["record"], out["info"]["setup_s"]
        extra["end_to_end"] = {m["name"]: harness._end_to_end(
            m["name"], rec, setup_s) for m in kw["metrics_e2e"]}
        extra["result"] = out["result"]
        return out

    if "--trace" in rest and rest[rest.index("--trace") + 1] == "1":
        harness.build = capture_build
        tr.reduce_file = reduce_more
    harness.run_cell = run_and_keep
    rc = run.main(rest)
    run.log("program " + json.dumps(extra))
    if out_path:
        pathlib.Path(out_path).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(out_path).write_text(json.dumps(extra, indent=1))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
