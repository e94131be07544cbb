"""Engine host path: mean wall time of the program's ``engine.finish``
span in the window (a request's pages freed and its lane released)."""

import program_trace as pt


def read(rec):
    ev = pt.window_spans(rec, "engine.finish")
    if not ev:
        return None
    return 1e3 * sum(e - s for _, s, e, _ in ev) / len(ev)
