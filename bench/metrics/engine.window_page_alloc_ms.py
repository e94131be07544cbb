"""Engine host path: mean wall time of the program's
``engine.window_page_alloc`` span in the window (a step's ring-pool
page allocation for the window layers: the ring table's read, the
allocator call, the read of its offsets, the table's upload)."""

import program_trace as pt


def read(rec):
    ev = pt.window_spans(rec, "engine.window_page_alloc")
    if not ev:
        return None
    return 1e3 * sum(e - s for _, s, e, _ in ev) / len(ev)
