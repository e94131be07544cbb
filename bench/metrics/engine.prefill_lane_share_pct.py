"""Engine: share of the window's lane-steps that fed a prompt token
(teacher-forced) rather than sampled one, from the lane counts the
program's ``engine.step`` spans carry."""

import program_trace as pt


def read(rec):
    ev = pt.window_spans(rec, "engine.step")
    if not ev:
        return None
    pre = sum(a.get("prefill_lanes", 0) for *_, a in ev)
    dec = sum(a.get("decode_lanes", 0) for *_, a in ev)
    return 100.0 * pre / (pre + dec) if pre + dec else None
