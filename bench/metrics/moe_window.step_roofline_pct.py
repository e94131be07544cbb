"""Decode step's share of its HBM roofline, for a sparse-expert stack
with window and full attention layers: the least bytes a step needs
(``cost_moe_window.step_min_bytes``: weights once, every held expert,
each lane's attended K/V by layer kind) at the chip's peak bandwidth,
over the device time of the decode program in the traced window.

A step's K/V tokens are averaged over the window: in full layers from
the harness's record of each step's lanes and positions, in window
layers from the ``window_tokens`` the program's ``engine.step`` spans
carry; times the traced decode calls."""

import cost_moe_window as cost
import program_trace as pt


def read(rec):
    red = rec["trace"]
    if red is None:
        return None
    m = red["modules"].get(rec["programs"]["step_fn"])
    if not m or not m["seconds"]:
        return None
    ev = pt.window_spans(rec, "engine.step")
    steps = [(n, c) for _, _, n, c in rec["steps"] if n]
    if not ev or not steps or any("window_tokens" not in a
                                  for *_, a in ev):
        return None
    full = sum(c + n for n, c in steps) / len(steps)
    window = sum(a["window_tokens"] for *_, a in ev) / len(ev)
    total = cost.step_min_bytes(rec["model"], full, window) * m["calls"]
    return 100.0 * total / rec["peaks"]["hbm_bytes_per_s"] / m["seconds"]
