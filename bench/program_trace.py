"""The program's own record of a run: its spans (``repro.obs``) and the
named scopes of its decode step.

Per-layer readers (``bench/metrics/``) take the program's spans from
its in-process span buffer after the run (``window_spans``): the
program times each span on ``time.perf_counter``, the harness's clock.
The same spans are on the profiler trace's host plane, but the
harness's reduction keeps only its own spans from there, so a reader
that sets a program span against the device trace maps it onto the
trace's host clock by the window's two ends, which both clocks saw
(``trace_clock``: ``rec["window"]`` and the ``bench.window`` span, a few
microseconds apart).  A program without these spans, and a buffer that
let go of part of the window, read as ``None``.

``trace_program.py`` reduces a trace file further, before the harness
deletes it: idle gaps labelled by the innermost program span
(``label_gaps``), and the decode program's device time split by named
scope (``hlo_op_scopes``, ``scope_times``).
"""

from __future__ import annotations

import bisect
import collections
import re

import reduce_trace as tr

# named scopes of the decode step (serving/decode.py, tp_layers.py);
# an op inside two takes the inner one
SCOPES = ("attention", "kv_write", "kv_gather", "ffn", "logits")
UNSCOPED = "unscoped"
PROGRAM_PREFIXES = ("engine.", "sched.", "engine_recovery.")
# spans inside ``engine.step`` that label its host work
STEP_PARTS = ("engine.d2h.", "engine.page_alloc", "engine.dispatch",
              "engine.emit")


def window_spans(rec: dict, *names: str) -> list | None:
    """The program's spans that opened in the window, as ``(name, start,
    end, args)`` in seconds on the harness clock; ``names`` keeps those
    named so, a name ending in ``.`` keeping every name it begins."""
    try:
        from repro import obs
    except ImportError:
        return None
    read = getattr(obs, "span_events", None)
    if read is None:
        return None
    t0, t1 = rec["window"]
    events = read(t0)
    if events is None:
        return None
    return [ev for ev in events if ev[1] < t1 and (not names or any(
        ev[0] == n or (n.endswith(".") and ev[0].startswith(n))
        for n in names))]


def trace_clock(rec: dict):
    """``t -> ns``: a harness-clock time on the trace's host clock, by
    the window's two ends."""
    t0, t1 = rec["window"]
    w0, w1 = rec["trace"]["window_ns"]
    scale = (w1 - w0) / (t1 - t0)
    return lambda t: w0 + (t - t0) * scale


def idle_ns(rec: dict, spans) -> int:
    """Device-idle nanoseconds inside the host intervals ``spans`` (on
    the harness clock; they must not overlap)."""
    ns = trace_clock(rec)
    tot = 0
    for _, s, e, _ in spans:
        a, b = round(ns(s)), round(ns(e))
        tot += (b - a) - tr.busy_within(rec["trace"], a, b)
    return tot


# ------------------------------------------------- trace file reductions
def host_spans(planes, prefixes=PROGRAM_PREFIXES) -> dict:
    """Host-plane spans whose names begin with one of ``prefixes``:
    ``name -> sorted [(start_ns, end_ns)]``."""
    out = collections.defaultdict(list)
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith(prefixes):
                    s = int(e.start_ns)
                    out[e.name].append((s, s + int(e.duration_ns)))
    return {k: sorted(v) for k, v in out.items()}


def label_gaps(red: dict, spans: dict) -> list:
    """The first device's idle gaps in the window as ``(start, end,
    label)``, each labelled by the innermost of ``spans`` (``name ->
    sorted intervals``, no two calls of one name overlapping) that holds
    its midpoint, as ``reduce_trace`` labels them by harness span."""
    w0, w1 = red["window_ns"]
    out, prev = [], w0
    for s, e in list(red["busy"]) + [[w1, w1]]:
        if s > prev:
            mid = (prev + s) // 2
            label, best = None, None
            for name, ivs in spans.items():
                i = bisect.bisect_right(ivs, (mid, float("inf"))) - 1
                if i >= 0 and ivs[i][0] <= mid < ivs[i][1]:
                    length = ivs[i][1] - ivs[i][0]
                    if best is None or length < best:
                        label, best = name, length
            out.append((prev, s, label))
        prev = max(prev, e)
    return out


def idle_by_program_span(red: dict, program: dict) -> dict:
    """Idle seconds by innermost program span, the longest gaps with
    theirs, and how the idle the harness's reduction labels
    ``ServingEngine.step`` splits among program spans."""
    by_prog = collections.Counter()
    step_split = collections.Counter()
    h_labels = label_gaps(red, red["spans"])
    p_labels = label_gaps(red, program)
    gaps = []
    for (a, b, h), (_, _, p) in zip(h_labels, p_labels):
        p = p or "no program span"
        by_prog[p] += (b - a) / 1e9
        gaps.append(((b - a) / 1e9, p))
        if h == "ServingEngine.step":
            step_split[p] += (b - a) / 1e9
    total = sum(step_split.values())
    finer = sum(v for k, v in step_split.items()
                if k.startswith(STEP_PARTS))
    return {"idle_by_program_span": by_prog.most_common(),
            "longest_gaps": [[p, d] for d, p in sorted(gaps)[::-1][:8]],
            "serving_engine_step_idle_s": total,
            "serving_engine_step_split": step_split.most_common(),
            "finer_than_engine_step_share": finer / total if total else None}


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def hlo_op_scopes(hlo_text: str, scopes=SCOPES) -> dict:
    """Each instruction of a compiled HLO module's text -> its named
    scope: the innermost of ``scopes`` in its ``op_name`` metadata; an
    instruction with none (a copy or a loop the compiler made) takes its
    first operand's own, else ``unscoped``."""
    own, first = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or line.lstrip().startswith(("HloModule", "//")):
            continue
        name, rest = m.groups()
        o = _OP_NAME.search(rest)
        hits = [p for p in (o.group(1).split("/") if o else ())
                if p in scopes]
        own[name] = hits[-1] if hits else None
        operands = _OPERAND.findall(rest.split("metadata=", 1)[0])
        first[name] = operands[0] if operands else None
    return {name: s or own.get(first[name]) or UNSCOPED
            for name, s in own.items()}


def self_times(ops) -> list:
    """``(name, start, duration)`` events of one device line, nested
    (a loop holds its body's ops) -> ``(name, self time)``: each
    event's duration less the durations of the events directly inside
    it."""
    evs = sorted(ops, key=lambda x: (x[1], -x[2]))
    selfs = [d for _, _, d in evs]
    stack = []
    for i, (_, s, d) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= d
        stack.append(i)
    return [(n, t) for (n, _, _), t in zip(evs, selfs)]


def scope_times(planes, module: str, scope_of: dict) -> dict:
    """The first device's op self time inside calls of ``module`` (by
    ``XLA Modules`` interval, on the device clock), summed by named
    scope in seconds, with the number of calls and the total."""
    for pl in planes:
        if not tr._DEVICE_PLANE.match(pl.name):
            continue
        lines = {ln.name: ln for ln in pl.lines}
        calls = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns))
                       for e in lines["XLA Modules"].events
                       if tr.module_name(e.name) == module)
        starts = [s for s, _ in calls]
        ops = []
        for e in lines["XLA Ops"].events:
            s = int(e.start_ns)
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < calls[i][1]:
                ops.append((tr.op_name(e.name), s, int(e.duration_ns)))
        by = collections.Counter()
        missing = set()
        for name, t in self_times(ops):
            if name not in scope_of:
                missing.add(name)
            by[scope_of.get(name, UNSCOPED)] += t / 1e9
        return {"calls": len(calls), "self_s": sum(by.values()),
                "module_s": sum(e - s for s, e in calls) / 1e9,
                "scopes_s": dict(by.most_common()),
                "ops_not_in_hlo": sorted(missing)[:20]}
    raise ValueError("trace holds no TPU device plane")
