"""The benchmark's Mellum2 cell at smoke size on the CPU: its traffic,
its byte count, and one whole run of the harness through the ring pool
and the held expert share, with the fp8 control in the comparison."""

import dataclasses
import functools
import json
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1] / "bench"
sys.path[:0] = [str(BENCH)]

import cost_moe_window as cost  # noqa: E402
import harness  # noqa: E402
import traffic  # noqa: E402

from repro.configs.mellum2_12b_a2_5b import ep4_config  # noqa: E402
from repro.models import transformer as T  # noqa: E402

CELL = "mellum2-longcode-closed"
# the Mellum2 pattern at smoke widths, 4 of 8 routed experts held, in
# float32: the served path then agrees with the reference's argmax at
# every sampled token (gap 0), while the fp8 control reads near 1
MODEL = dict(
    family="moe", num_layers=8, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=32, vocab_size=128,
    pattern=[["local_attn", "moe"]] * 3 + [["attn", "moe"]], window=16,
    num_experts=4, router_experts=8, expert_first=0, top_k=2, mlp="swiglu",
    norm="rmsnorm", use_rope=True, rope_theta=500000.0, yarn_factor=16.0,
    yarn_original_max_pos=8192, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782, tie_embeddings=False,
    qkv_bias=False, dtype="float32", page_size=4, kv_dtype="bf16")
LIMIT = 0.05


def _bench() -> dict:
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_longcode_mix_mixes_short_and_long_past_the_window():
    """The cell's traffic as the benchmark states it: 18% of answers of
    512 tokens or fewer, the longest sequence 6740 tokens, and 57% of
    lane-steps past the 1024-token window."""
    mix = traffic.load_mix("longcode-closed")
    pairs = traffic.length_pairs(mix["clients"] * mix["requests_per_client"],
                                 mix)
    lens = [p + o for p, o in pairs]
    assert np.mean([o <= 512 for _, o in pairs]) == pytest.approx(0.18,
                                                                  abs=0.005)
    assert max(lens) == 6740 < 8192 - 1
    past = sum(max(0, n - 1024) for n in lens) / sum(lens)
    assert 0.56 < past < 0.58


@pytest.mark.parametrize("name", ["longcode-closed", "humaneval-sat"])
def test_new_mixes_repeat_from_seed(name):
    mix = traffic.load_mix(name)
    a = traffic.make_schedule(mix, 2**31 + 21, 50, 98304)
    b = traffic.make_schedule(mix, 2**31 + 21, 50, 98304)

    def flat(s):
        return [(r.prompt, r.max_new, r.due)
                for r in s.warm + s.window + sum(s.clients, [])]
    assert flat(a) == flat(b) and flat(a)


def test_weight_bytes_are_the_served_weights():
    """``cost_moe_window`` counts the weights a step multiplies by: every
    parameter the program holds but the embedding (a step reads one row
    of it) and the norms."""
    cfg = ep4_config()
    model = json.loads((BENCH / "configs" / "mellum2-12b-ep4.json")
                       .read_text())["model"]
    assert harness.model_config(model, "m") == dataclasses.replace(
        cfg, name="m")
    pshape = jax.eval_shape(functools.partial(
        T.init_params, cfg, jax.random.PRNGKey(0)))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pshape))
    embed = pshape["embed"].size * 2
    norms = (2 * cfg.num_layers + 1) * cfg.d_model * 4
    assert cost.weight_bytes(model) == held - embed - norms
    assert cost.layer_kinds(model) == (7, 21)


def test_mellum_cell_runs_at_smoke_size_and_the_control_fails():
    """A whole harness run of the cell's traffic at smoke size: no
    compile in the window, both pools balanced, the readers of the
    program's window spans read, and with the fp8 control in the
    program's place the comparison reads not correct while the
    program's own reading stays under the limit."""
    mix = traffic.load_mix("longcode-closed")
    mix.update(prompt_len=dict(median=10, sigma=0.6, min=2, max=30),
               output_len=dict(median=40, sigma=1.0, min=2, max=150),
               warm_s=1.5, clients=4, requests_per_client=4)
    config = {"name": "mellum-smoke", "model": MODEL, "reference": "mellum2",
              "serving": {"lanes": 4, "max_seq": 256, "pages_per_sb": 4},
              "limits": {"max_logit_gap": LIMIT}}
    bench = _bench()
    e2e = [m for m in bench["end_to_end"] if CELL in m.get("workloads", [CELL])]
    out = harness.run_cell({"name": CELL}, config, mix, seed=2**31 + 99,
                           seconds=6.0, trace=False, t_process=time.time(),
                           metrics_e2e=e2e, control=True, log=lambda m: None)
    res = out["result"]
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["checks"]["occupancy_mismatch"]["value"] == 0
    assert out["info"]["program_max_logit_gap"] < LIMIT
    assert res["checks"]["max_logit_gap"]["value"] > 5 * LIMIT
    assert res["correct"] is False
    assert set(res["metrics"]) == {"out_tok_per_s", "itl_p98_ms", "setup_s"}
    rec = out["record"]
    share = harness.load_reader("engine.window_lane_share_pct")(rec)
    assert 0 < share < 100
    assert harness.load_reader("engine.window_page_alloc_ms")(rec) > 0
    # no trace in this run: the roofline reader reads nothing
    assert harness.load_reader("moe_window.step_roofline_pct")(rec) is None
