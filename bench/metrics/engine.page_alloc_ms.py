"""Engine host path: mean wall time of the program's ``engine.page_alloc``
span in the window (a step's lane page allocation: the allocator call,
the read of its offsets and of the block table, the table's upload)."""

import program_trace as pt


def read(rec):
    ev = pt.window_spans(rec, "engine.page_alloc")
    if not ev:
        return None
    return 1e3 * sum(e - s for _, s, e, _ in ev) / len(ev)
