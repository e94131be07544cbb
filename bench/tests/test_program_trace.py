"""The program's spans and scopes as the per-layer readers and
``trace_program.py`` see them, on synthetic planes and records (no
chip): ``python -m pytest bench/tests``."""

import collections
import json
import pathlib

import pytest

import smoke  # noqa: F401  (puts bench/ and src/ on the path)
import harness
import program_trace as pt
import reduce_trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
Ev = collections.namedtuple("Ev", "name start_ns duration_ns")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")


def test_reduction_of_the_recorded_trace_is_unchanged():
    """Every field ``reduce_file`` gives for the recorded TPU trace,
    against the values it gave when the program's spans were added."""
    red = tr.reduce_file(DATA / "small.xplane.pb", set(harness.SPANS))
    got = json.loads(json.dumps(dict(red, busy=[list(b) for b in
                                                 red["busy"]])))
    want = json.loads((DATA / "small_reduced.json").read_text())
    assert set(got) == set(want)
    for key in want:
        assert got[key] == want[key], key


def test_program_reductions_read_the_recorded_trace():
    """The recorded TPU trace through ``trace_program.py``'s reductions
    (it has no program spans: the harness's step span stands in)."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(DATA / "small.xplane.pb"))
                  .planes)
    red = tr.reduce_planes(planes, set(harness.SPANS))
    spans = pt.host_spans(planes, ("ServingEngine.",))
    assert len(spans["ServingEngine.step"]) == 10
    idle = pt.idle_by_program_span(red, spans)
    assert idle["serving_engine_step_idle_s"] == pytest.approx(
        dict(red["idle_by_span"]).get("ServingEngine.step", 0.0))
    out = pt.scope_times(planes, "jit_step", {})
    assert out["calls"] == 10
    assert out["scopes_s"] == {pt.UNSCOPED: pytest.approx(out["self_s"])}
    assert out["self_s"] == pytest.approx(out["module_s"], rel=0.01)


# ------------------------------------------------- a trace with both
def _planes():
    """Window 100..400 ns.  Device: a decode call 100..200 whose loop
    (``while.1``) holds two ops, an allocator call 250..260.  Host: the
    harness's ``ServingEngine.step`` spans and the program's spans
    inside them."""
    dev = Plane("/device:TPU:0", [
        Line("XLA Modules", [Ev("jit_step(1)", 100, 100),
                             Ev("jit_alloc(2)", 250, 10)]),
        Line("XLA Ops", [Ev("%while.1 = (s32[]) while(%t)", 100, 80),
                         Ev("fusion.2", 105, 30), Ev("copy.3", 140, 30),
                         Ev("fusion.4", 180, 20), Ev("scatter", 250, 10)])])
    host = Plane("/host:CPU", [Line("python", [
        Ev("bench.window", 100, 300),
        Ev("ServingEngine.step", 95, 250),        # 95..345
        Ev("engine.step", 96, 248),
        Ev("engine.dispatch", 97, 5),
        Ev("engine.d2h.tokens", 110, 110),         # 110..220
        Ev("engine.emit", 220, 20),                 # 220..240
        Ev("engine.page_alloc", 245, 60),           # 245..305
        Ev("engine.d2h.offs", 262, 10),             # 262..272
        Ev("sched.step", 90, 300)])])
    return [dev, host]


def test_program_spans_label_the_idle_the_harness_gives_to_the_step():
    planes = _planes()
    red = tr.reduce_planes(planes, set(harness.SPANS))
    prog = pt.host_spans(planes)
    assert set(prog) == {"engine.step", "engine.dispatch",
                         "engine.d2h.tokens", "engine.emit",
                         "engine.page_alloc", "engine.d2h.offs",
                         "sched.step"}
    out = pt.idle_by_program_span(red, prog)
    # gaps: 200..250 (mid 225, engine.emit), 260..400 (mid 330,
    # ServingEngine.step holds it; engine.step is the innermost program
    # span there)
    assert dict(out["idle_by_program_span"]) == {
        "engine.emit": pytest.approx(50e-9),
        "engine.step": pytest.approx(140e-9)}
    assert out["longest_gaps"] == [["engine.step", pytest.approx(140e-9)],
                                   ["engine.emit", pytest.approx(50e-9)]]
    assert out["serving_engine_step_idle_s"] == pytest.approx(190e-9)
    assert out["finer_than_engine_step_share"] == pytest.approx(50 / 190)
    # the harness's own fields are those of a trace without the spans
    host = planes[1]
    bare = [planes[0], Plane(host.name, [Line("python", [
        e for e in host.lines[0].events
        if not e.name.startswith(pt.PROGRAM_PREFIXES)])])]
    base = tr.reduce_planes(bare, set(harness.SPANS))
    for key in ("busy_s", "window_s", "modules", "top_ops", "idle_by_span",
                "longest_gaps", "spans"):
        assert red[key] == base[key], key


HLO = """\
HloModule jit_step
%fused_computation.1 (param_0.1: bf16[4]) -> bf16[4] {
  %param_0.1 = bf16[4]{0} parameter(0)
  ROOT %copy.9 = bf16[4]{0} copy(%param_0.1), metadata={op_name="jit(step)/while/body/attention/kv_gather/gather"}
}
ENTRY %main (p: bf16[4]) -> bf16[4] {
  %t = (s32[]) tuple(%c)
  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/while"}
  %fusion.2 = bf16[4]{0} fusion(%p), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/attention/kv_gather/gather"}
  %copy.3 = bf16[4]{0} copy(%fusion.2)
  ROOT %fusion.4 = bf16[4]{0} fusion(%copy.3), kind=kLoop, metadata={op_name="jit(step)/logits/dot_general"}
  %fusion.5 = bf16[4]{0} fusion(%p), metadata={op_name="jit(step)/while/body/attention/exp"}
}
"""


def test_scopes_from_op_metadata():
    sc = pt.hlo_op_scopes(HLO)
    assert sc["fusion.2"] == "kv_gather"       # inner of two scopes
    assert sc["fusion.5"] == "attention"
    assert sc["fusion.4"] == "logits"
    assert sc["copy.3"] == "kv_gather"         # takes its operand's
    assert sc["while.1"] == pt.UNSCOPED        # its operand has none
    assert sc["copy.9"] == "kv_gather"


def test_self_time_of_nested_ops():
    ops = [("while.1", 100, 80), ("fusion.2", 105, 30), ("copy.3", 140, 30),
           ("fusion.4", 180, 20)]
    assert dict(pt.self_times(ops)) == {"while.1": 20, "fusion.2": 30,
                                        "copy.3": 30, "fusion.4": 20}


def test_scope_split_accounts_for_the_whole_step():
    planes = _planes()
    sc = pt.hlo_op_scopes(HLO)
    out = pt.scope_times(planes, "jit_step", sc)
    assert out["calls"] == 1
    assert out["scopes_s"] == {"kv_gather": pytest.approx(60e-9),
                               pt.UNSCOPED: pytest.approx(20e-9),
                               "logits": pytest.approx(20e-9)}
    assert sum(out["scopes_s"].values()) == pytest.approx(out["self_s"])
    assert out["self_s"] == pytest.approx(100e-9)
    assert out["ops_not_in_hlo"] == []


# ------------------------------------------------------ the readers
def _rec(trace=True, crashes=()):
    """A record with the window 1.0..4.0 s on the harness clock, and a
    trace whose window is 100..400 ns on its host clock (1 s = 100 ns),
    busy 100..200 and 250..260."""
    red = tr.reduce_planes(_planes(), set(harness.SPANS)) if trace else None
    return {"window": (1.0, 4.0), "trace": red, "crashes": list(crashes),
            "programs": {"step_fn": "jit_step", "alloc": ["jit_alloc"]}}


EVENTS = [
    ("engine.step", 1.0, 2.5, {"prefill_lanes": 3, "decode_lanes": 1}),
    ("engine.d2h.pos", 1.05, 1.1, {}),
    ("engine.d2h.tokens", 1.1, 2.2, {}),        # idle 2.0..2.2 -> 20 ns
    ("engine.page_alloc", 2.45, 3.05, {}),
    ("engine.d2h.offs", 2.62, 2.72, {}),        # idle 2.62..2.72 -> 10 ns
    ("engine.step", 2.5, 3.5, {"prefill_lanes": 0, "decode_lanes": 4}),
    ("engine.finish", 3.5, 3.6, {}),
    ("engine.finish", 3.6, 3.9, {}),
    ("engine_recovery.mark_sweep", 3.0, 3.9, {}),   # idle 90 ns
    ("engine.step", 0.5, 0.9, {"prefill_lanes": 9, "decode_lanes": 9}),
]


@pytest.fixture
def span_buffer(monkeypatch):
    from repro import obs

    def install(events):
        def read(since=float("-inf")):
            return None if events is None else [e for e in events
                                                if e[1] >= since]
        monkeypatch.setattr(obs, "span_events", read)
    return install


@pytest.mark.parametrize("name,want", [
    ("engine.d2h_reads_per_step", 3 / 2),
    ("engine.d2h_idle_ms_per_step", 30e-6),
    ("engine.page_alloc_ms", 600.0),
    ("engine.finish_ms", 200.0),
    ("engine.prefill_lane_share_pct", 100 * 3 / 8),
    ("recovery.host_idle_s", 90e-9 / 2),
])
def test_reader_on_a_record(name, want, span_buffer):
    span_buffer(EVENTS)
    rec = _rec(crashes=[{}, {}])
    assert harness.load_reader(name)(rec) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "engine.d2h_reads_per_step", "engine.d2h_idle_ms_per_step",
    "engine.page_alloc_ms", "engine.finish_ms",
    "engine.prefill_lane_share_pct", "recovery.host_idle_s"])
@pytest.mark.parametrize("case", ["no_spans", "buffer_let_go", "no_trace"])
def test_reader_leaves_out_what_it_cannot_read(name, case, span_buffer,
                                               monkeypatch):
    from repro import obs
    if case == "no_spans":          # a program from before the spans
        monkeypatch.delattr(obs, "span_events")
    else:
        span_buffer(None if case == "buffer_let_go" else EVENTS)
    rec = _rec(trace=case != "no_trace", crashes=[{}])
    value = harness.load_reader(name)(rec)
    trace_only = name in ("engine.d2h_idle_ms_per_step",
                          "recovery.host_idle_s")
    if case == "no_trace" and not trace_only:
        assert value is not None
    else:
        assert value is None
