"""Units for the unified observability layer (``repro.obs``).

Covers the registry primitives (near-zero disabled path, get-or-create
identity, named resets that raise on unknown metrics — the
``benchmarks/run.py`` reset hazard), span/phase recording with
Chrome-trace export, the live :class:`WasteMonitor`'s parity with the
persist-lint ``DurabilityShadow`` on one and the same trace, and the
exact recovery-stats contract (phase names + stat keys pinned, so a
rename fails loudly instead of silently vanishing from dashboards).
"""

import contextlib
import json
import os
import tempfile
import time

import numpy as np
import pytest

from repro import obs
from repro.analysis.persist_lint import DurabilityShadow
from repro.analysis.trace import attach_tracer
from repro.core import recovery
from repro.core.ralloc import Ralloc
from repro.obs.registry import Registry, UnknownMetric

MB = 1 << 20


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------
def test_counter_gauge_histogram_roundtrip():
    reg = Registry()
    c = reg.counter("t.hits")
    assert c is reg.counter("t.hits")        # stable identity (cacheable)
    c.inc()
    c.inc(3)
    reg.gauge("t.depth").set(7)
    h = reg.histogram("t.lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["t.hits"] == 4
    assert snap["gauges"]["t.depth"] == 7
    hs = snap["histograms"]["t.lat"]
    assert hs["count"] == 4 and hs["min"] == 1.0 and hs["max"] == 4.0
    assert hs["mean"] == 2.5 and hs["p50"] == 3.0


def test_disabled_registry_records_nothing():
    reg = Registry(enabled=False)
    reg.counter("t.c").inc()
    reg.gauge("t.g").set(5)
    reg.histogram("t.h").observe(1.0)
    with reg.span("t.phase") as sp:
        sp.add(3)
    assert sp.seconds >= 0.0                 # spans still time when disabled
    snap = reg.snapshot()
    assert snap["counters"]["t.c"] == 0
    assert snap["gauges"]["t.g"] == 0
    assert snap["histograms"] == {} and snap["phases"] == {}
    assert reg.chrome_trace()["traceEvents"] == []


def test_reset_unknown_metric_raises():
    reg = Registry()
    reg.counter("t.known")
    with pytest.raises(UnknownMetric):
        reg.reset("t.known", "t.never_registered")
    reg.gauge_fn("t.fn_gauge", lambda: 42)
    with pytest.raises(UnknownMetric):
        reg.reset("t.fn_gauge")              # callback gauges can't reset
    reg.register_source("t.src_no_reset", read=lambda: 1)
    with pytest.raises(UnknownMetric):
        reg.reset("t.src_no_reset")


def test_source_reset_routes_to_owner():
    reg = Registry()
    box = {"n": 9}
    reg.register_source("t.src", read=lambda: box["n"],
                        reset=lambda: box.update(n=0))
    assert reg.snapshot()["counters"]["t.src"] == 9
    reg.reset("t.src")
    assert box["n"] == 0
    # reset_all leaves sources alone (the owner resets by name)
    box["n"] = 5
    reg.reset_all()
    assert box["n"] == 5


def test_heap_registers_resettable_sources():
    """The live heap's n_flush/n_fence/... are registry sources: the
    benchmark harness resets them BY NAME through the registry (typo →
    UnknownMetric) instead of the old blind reset_counters() call."""
    r = Ralloc(None, 8 * MB)
    p = r.malloc(64)
    r.write_word(p, 1)
    r.flush_range(p, 1)
    r.fence()
    assert r.mem.n_flush > 0 and r.mem.n_fence > 0
    obs.reset("heap.flush", "heap.fence", "heap.cas", "heap.drain")
    assert r.mem.n_flush == 0 and r.mem.n_fence == 0
    assert r.mem.n_cas == 0 and r.mem.n_drain == 0
    snap = obs.snapshot()
    assert snap["counters"]["heap.flush"] == 0
    with pytest.raises(UnknownMetric):
        obs.reset("heap.flushh")
    r.close()


# ---------------------------------------------------------------------------
# spans, phases and Chrome-trace export
# ---------------------------------------------------------------------------
def test_span_phases_accumulate_and_trace_exports():
    reg = Registry()
    for _ in range(3):
        with reg.span("phase.one", tag="x") as sp:
            sp.add(2)
    snap = reg.snapshot()
    row = snap["phases"]["phase.one"]
    assert row["calls"] == 3 and row["items"] == 6
    assert row["seconds"] >= 0.0
    trace = reg.chrome_trace()
    assert len(trace["traceEvents"]) == 3
    ev = trace["traceEvents"][0]
    assert ev["name"] == "phase.one" and ev["ph"] == "X"
    assert ev["dur"] >= 0 and ev["args"]["items"] == 2
    # loadable: a JSON round-trip preserves the Chrome trace shape
    loaded = json.loads(json.dumps(trace))
    assert {e["name"] for e in loaded["traceEvents"]} == {"phase.one"}
    reg.reset_all()
    assert reg.chrome_trace()["traceEvents"] == []
    assert reg.snapshot()["phases"] == {}


def _logging_annotator(log):
    @contextlib.contextmanager
    def annotate(name):
        log.append(("enter", name))
        yield
        log.append(("exit", name))
    return annotate


def test_annotator_wraps_each_span_in_order():
    reg = Registry()
    log = []
    reg.annotator = _logging_annotator(log)
    with reg.span("t.outer"):
        with reg.span("t.inner") as sp:
            log.append("work")
            sp.args.update(lanes=3)
    assert log == [("enter", "t.outer"), ("enter", "t.inner"), "work",
                   ("exit", "t.inner"), ("exit", "t.outer")]
    # the annotator changes nothing the registry records
    assert reg.snapshot()["phases"]["t.inner"]["calls"] == 1
    inner = [e for e in reg.chrome_trace()["traceEvents"]
             if e["name"] == "t.inner"]
    assert inner[0]["args"] == {"lanes": 3}


@pytest.mark.parametrize("where", ["registry", "default"])
def test_annotator_is_never_called_while_disabled(where):
    log = []
    if where == "registry":
        reg = Registry(enabled=False)
        reg.annotator = _logging_annotator(log)
        span = reg.span
    else:
        prev = obs.get_registry().annotator
        obs.set_annotator(_logging_annotator(log))
        obs.disable()
        span = obs.span
    try:
        with span("t.quiet") as sp:
            pass
    finally:
        if where == "default":
            obs.enable()
            obs.set_annotator(prev)
    assert log == [] and sp.seconds >= 0.0


def test_span_buffer_keeps_the_newest_and_says_what_it_let_go(monkeypatch):
    monkeypatch.setattr(Registry, "TRACE_CAP", 4)
    reg = Registry()
    for i in range(2):
        with reg.span(f"t.old{i}"):
            pass
    mid = time.perf_counter()
    for i in range(4):
        with reg.span(f"t.new{i}", i=i):
            pass
    kept = reg.span_events(mid)
    assert [e[0] for e in kept] == [f"t.new{i}" for i in range(4)]
    assert all(mid <= s <= e for _, s, e, _ in kept)
    assert [e[3] for e in kept] == [{"i": i} for i in range(4)]
    assert reg.span_events() is None         # the two old ones are gone
    trace = reg.chrome_trace()
    assert trace["otherData"]["dropped"] == 2
    assert [e["name"] for e in trace["traceEvents"]] == \
        [f"t.new{i}" for i in range(4)]
    reg.reset_all()
    assert reg.span_events() == []


# ---------------------------------------------------------------------------
# WasteMonitor ≡ DurabilityShadow (two implementations, one trace)
# ---------------------------------------------------------------------------
def test_waste_monitor_parity_with_shadow_diag():
    """Replay one real allocator trace through BOTH waste analyses: the
    streaming monitor (repro.obs.waste) and the batch shadow
    (analysis.persist_lint).  Their diagnostics must agree exactly."""
    r = Ralloc(None, 8 * MB)
    tr = attach_tracer(r)
    ptrs = [r.malloc(64) for _ in range(20)]
    for i, p in enumerate(ptrs):
        r.write_word(p, i)
        r.flush_range(p, 1)
    r.fence()
    r.fence()                          # deliberate: one empty fence
    r.flush_range(ptrs[0], 1)          # deliberate: one redundant flush
    for p in ptrs[::2]:
        r.free(p)
    r.set_root(0, ptrs[1])
    r.mem.tracer = None
    events = tr.events
    assert any(e.kind == "write" for e in events)

    sh = DurabilityShadow(tr.base)
    mon = obs.WasteMonitor()           # standalone (no registry binding)
    for ev in events:
        mon.record(ev.kind, ev.addr, ev.value, ev.label, ev.info)
        if ev.kind == "write":
            sh.write(ev.addr, ev.value)
        elif ev.kind == "flush":
            sh.flush(ev.addr)
        elif ev.kind == "fence":
            sh.fence()
        elif ev.kind == "drain":
            sh.drain()
        elif ev.kind == "crash":
            sh.crash()
    assert mon.diag == dict(sh.diag)
    assert mon.diag["empty_fences"] >= 1
    assert mon.diag["redundant_flushes"] >= 1
    r.close()


def test_waste_monitor_gauges_live_in_snapshot():
    reg = Registry()
    r = Ralloc(None, 8 * MB)
    mon = obs.attach_waste_monitor(r.mem, registry=reg)
    p = r.malloc(64)
    r.write_word(p, 7)
    r.flush_range(p, 1)
    r.fence()
    r.mem.tracer = None
    snap = reg.snapshot()
    assert snap["gauges"]["persist.writes"] == mon.writes > 0
    assert snap["gauges"]["persist.flushes"] == mon.flushes > 0
    assert snap["gauges"]["persist.redundant_flushes"] == 0
    assert snap["gauges"]["persist.empty_fences"] == 0
    r.close()


# ---------------------------------------------------------------------------
# recovery stats contract: phase names and stat keys are pinned
# ---------------------------------------------------------------------------
def test_recovery_stats_keys_and_phase_names_pinned():
    """Exact-set pin: a renamed or dropped recovery stat/phase breaks
    this test instead of silently disappearing from the snapshot."""
    path = tempfile.mktemp()
    r = Ralloc(path, 8 * MB, sim_nvm=True, seed=7)
    p = r.malloc(64)
    r.write_word(p, 123)
    r.flush_range(p, 1)
    r.fence()
    r.set_root(0, p)
    r.heap.crash()
    del r
    r2 = Ralloc(path, 8 * MB, sim_nvm=True, seed=8)
    assert r2.dirty_restart
    r2.get_root(0)
    obs.reset_all()
    stats = r2.recover()
    assert set(stats) == {
        "reachable_blocks", "free_superblocks", "free_runs",
        "index_records", "index_retrims", "index_pruned",
        "trie_records", "trie_retrims", "trie_pruned",
        "partial_superblocks", "full_superblocks", "large_blocks",
        "shared_spans", "mark_seconds", "sweep_seconds", "total_seconds",
        "phases",
    }
    assert recovery.PHASES == (
        "prune_index", "prune_trie", "mark", "sweep", "reconstruct",
        "retrim_index", "retrim_trie", "drain")
    assert set(stats["phases"]) == set(recovery.PHASES)
    for name, row in stats["phases"].items():
        assert set(row) == {"seconds", "items"}
        assert row["seconds"] >= 0.0
    # the same phases accumulated into the registry under recovery.*
    reg_phases = obs.snapshot()["phases"]
    assert {f"recovery.{n}" for n in recovery.PHASES} <= set(reg_phases)
    r2.close()
    os.unlink(path)


# ---------------------------------------------------------------------------
# allocator counters flow end to end
# ---------------------------------------------------------------------------
def test_allocator_counters_populate_snapshot():
    obs.reset_all()
    r = Ralloc(None, 8 * MB)
    ptrs = [r.malloc(64) for _ in range(10)]
    for p in ptrs:
        r.free(p)
    big = r.malloc(3 * MB)
    r.free(big)
    c = obs.snapshot()["counters"]
    assert c["alloc.small"] == 10 and c["alloc.large"] == 1
    assert c["alloc.tcache_hit"] + c["alloc.tcache_miss"] == 10
    assert c["alloc.watermark_growth_sbs"] > 0
    assert c["heap.flush"] > 0 and c["heap.fence"] > 0
    r.close()
