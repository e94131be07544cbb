"""Bytes the decode step needs, from a configuration's shapes, for a
stack of sparse-expert layers whose attention is window or full by
layer (Mellum2-style: a ``pattern`` of ``local_attn`` and ``attn``
mixers, every FFN ``moe``, SwiGLU experts).

Counts are of what the algorithm needs, not of what the program does:
every weight a step multiplies by, read once (attention, the float32
router, every held expert, the output head; at 16 lanes and top-8 of
64 an expert is left without a token with probability (56/64)^16, 12%,
so a step reads nearly every held expert anyway), and per lane and
layer the K/V of the tokens it attends: position + 1 in a full layer,
at most the window in a window layer, the newest token's being the
one the step writes.  The program's whole-table gather is not counted.
``bench/cost.py`` counts the dense stacks.
"""

from __future__ import annotations

import numpy as np

F32 = 4


def _itemsize(model: dict) -> int:
    return int(np.dtype(model.get("dtype", "bfloat16")).itemsize)


def layer_kinds(model: dict) -> tuple[int, int]:
    """(full-attention layers, window layers)."""
    pat = model["pattern"]
    mixers = [pat[i % len(pat)][0] for i in range(model["num_layers"])]
    return mixers.count("attn"), mixers.count("local_attn")


def weight_bytes(model: dict) -> int:
    d, h, kv, dh, f = (model["d_model"], model["num_heads"],
                       model["num_kv_heads"], model["head_dim"],
                       model["d_ff"])
    routed = model.get("router_experts") or model["num_experts"]
    attn = d * (h + 2 * kv) * dh + h * dh * d
    experts = model["num_experts"] * 3 * d * f
    L = model["num_layers"]
    return ((L * (attn + experts) + model["vocab_size"] * d)
            * _itemsize(model) + L * d * routed * F32)


def kv_bytes_per_token_layer(model: dict) -> int:
    return 2 * model["num_kv_heads"] * model["head_dim"] * _itemsize(model)


def step_min_bytes(model: dict, full_tokens: float,
                   window_tokens: float) -> float:
    """Least HBM bytes of one decode step whose lanes attend
    ``full_tokens`` K/V tokens in each full layer (the sum of their
    positions + 1) and ``window_tokens`` in each window layer (the sum
    of min(position + 1, window))."""
    n_full, n_win = layer_kinds(model)
    return weight_bytes(model) + kv_bytes_per_token_layer(model) * (
        n_full * full_tokens + n_win * window_tokens)
