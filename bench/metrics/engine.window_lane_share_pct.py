"""Engine: share of the window's active lane-steps whose position is at
or past the window of the window layers (their ring has wrapped), from
the ``window_lanes`` the program's ``engine.step`` spans carry over the
lanes each stepped (prefill + decode)."""

import program_trace as pt


def read(rec):
    ev = pt.window_spans(rec, "engine.step")
    if not ev or any("window_lanes" not in a for *_, a in ev):
        return None
    past = sum(a["window_lanes"] for *_, a in ev)
    lanes = sum(a.get("prefill_lanes", 0) + a.get("decode_lanes", 0)
                for *_, a in ev)
    return 100.0 * past / lanes if lanes else None
