"""Recovery: device-idle time inside the program's ``engine_recovery.*``
phase spans in the traced window, mean per crash."""

import program_trace as pt


def read(rec):
    red = rec["trace"]
    if red is None or not rec["crashes"]:
        return None
    ev = pt.window_spans(rec, "engine_recovery.")
    if not ev:
        return None
    return pt.idle_ns(rec, ev) / 1e9 / len(rec["crashes"])
