"""Engine host path: device->host reads per decode step, from the
program's own spans: its ``engine.d2h.*`` spans (one per read) over its
``engine.step`` spans, both opened in the window."""

import program_trace as pt


def read(rec):
    ev = pt.window_spans(rec, "engine.step", "engine.d2h.")
    steps = sum(1 for e in ev or () if e[0] == "engine.step")
    if not steps:
        return None
    return (len(ev) - steps) / steps
