"""Rotary position embeddings (RoPE), applied in fp32 for stability.

YaRN (arXiv:2309.00071), as HF ``transformers``' ``_compute_yarn_parameters``
computes it: each frequency blends the original one (extrapolation) and
the one divided by ``factor`` (interpolation) along a linear ramp
between the correction dims of ``beta_fast`` and ``beta_slow`` rotations
over ``original_max_pos`` positions (floored / ceiled), and cos and sin
are scaled by ``attention_factor``.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_freqs(head_dim: int, theta: float = 1e4):
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def yarn_freqs(head_dim: int, theta: float, factor: float,
               original_max_pos: int, beta_fast: float,
               beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies [head_dim / 2] (float32)."""
    half = head_dim // 2

    def correction_dim(rotations):
        return (head_dim * math.log(original_max_pos
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    extra = np.float32(theta) ** (-np.arange(0, head_dim, 2,
                                             dtype=np.float32) / head_dim)
    inter = extra / np.float32(factor)
    keep = 1.0 - ramp                # share of the original frequency
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def layer_rope(cfg, local: bool) -> dict:
    """``apply_rope`` keywords of one attention layer: YaRN on the full
    layers of a configuration that scales them, plain RoPE elsewhere."""
    if local or not cfg.yarn_factor:
        return {"theta": cfg.rope_theta}
    return {"theta": cfg.rope_theta,
            "freqs": yarn_freqs(cfg.head_dim, cfg.rope_theta,
                                cfg.yarn_factor, cfg.yarn_original_max_pos,
                                cfg.yarn_beta_fast, cfg.yarn_beta_slow),
            "scale": cfg.yarn_attention_factor}


def apply_rope(x, positions, theta: float = 1e4, *, freqs=None,
               scale: float = 1.0):
    """x: [..., S, H, Dh]; positions: broadcastable to [..., S].  ``freqs``
    replaces the plain inverse frequencies; ``scale`` multiplies cos and
    sin."""
    dh = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(dh, theta)                  # [dh/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, dh/2]
    cos = jnp.cos(ang)[..., None, :]                   # [..., S, 1, dh/2]
    sin = jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
