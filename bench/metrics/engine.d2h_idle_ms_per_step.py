"""Engine host path: device-idle time inside the program's
``engine.d2h.*`` spans (its device->host reads) in the traced window,
per call of the decode program."""

import program_trace as pt


def read(rec):
    red = rec["trace"]
    if red is None:
        return None
    steps = red["modules"].get(rec["programs"]["step_fn"], {}).get("calls")
    ev = pt.window_spans(rec, "engine.d2h.")
    if not steps or ev is None:
        return None
    return pt.idle_ns(rec, ev) / 1e6 / steps
