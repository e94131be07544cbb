"""Model configuration covering all ten assigned architectures.

A model is a stack of (mixer, ffn) layer specs cycled from ``pattern``:

  mixer ∈ {"attn", "local_attn", "mamba2", "rglru"}
  ffn   ∈ {"mlp", "moe", "none"}

Uniform stacks (pattern length 1) scan over layers; hybrid stacks
(RecurrentGemma's 2×RG-LRU : 1×local-attn) scan over *pattern units*
with any remainder layers unrolled.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int = 0
    num_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    # layer pattern: tuple of (mixer, ffn) cycled over layers
    pattern: tuple[tuple[str, str], ...] = (("attn", "mlp"),)
    # attention options
    qkv_bias: bool = False
    use_rope: bool = True
    rope_theta: float = 1e4
    # YaRN on the full-attention layers (arXiv:2309.00071; 0 = plain
    # RoPE); window layers keep plain RoPE
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    causal: bool = True
    window: int = 0                # local-attention window (0 = full)
    # ffn options
    mlp: str = "swiglu"            # swiglu | squared_relu | gelu
    norm: str = "rmsnorm"
    tie_embeddings: bool = False
    # moe
    num_experts: int = 0           # experts held here
    router_experts: int = 0        # experts routed over (0 = num_experts)
    expert_first: int = 0          # id of the first expert held here
    top_k: int = 0
    expert_pad: int = 0            # zero experts padding E to a TP multiple
    capacity_factor: float = 1.25
    moe_dispatch: str = "local"    # local (per batch row; §Perf B5) | global
    # ssm (mamba2)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    conv_width: int = 4
    expand: int = 2
    # hybrid (rg-lru)
    lru_width: int = 0
    # modality frontend stub: None | "audio" | "vision"
    frontend: str | None = None
    # numerics / memory
    dtype: Any = jnp.bfloat16
    remat: str = "unit"            # none | unit (checkpoint each pattern unit)
    attn_impl: str = "chunked"     # chunked (flash-style) | naive
    # serving
    page_size: int = 128           # KV-arena tokens per page
    kv_dtype: str = "bf16"         # bf16 | int8 (per-slot-per-head scales)

    # ------------------------------------------------------------------
    @property
    def layer_specs(self) -> tuple[tuple[str, str], ...]:
        m = len(self.pattern)
        return tuple(self.pattern[i % m] for i in range(self.num_layers))

    @property
    def full_units(self) -> int:
        return self.num_layers // len(self.pattern)

    @property
    def tail_specs(self) -> tuple[tuple[str, str], ...]:
        r = self.num_layers % len(self.pattern)
        return self.pattern[:r]

    @property
    def attn_layers(self) -> int:
        return sum(1 for mx, _ in self.layer_specs
                   if mx in ("attn", "local_attn"))

    @property
    def routed_experts(self) -> int:
        return self.router_experts or self.num_experts

    @property
    def has_full_attn(self) -> bool:
        return any(mx == "attn" for mx, _ in self.layer_specs)

    @property
    def ring_pages(self) -> int:
        """Ring slots a lane's window layers keep (0: no window layer):
        every position inside the window lies on one of them."""
        if not any(mx == "local_attn" for mx, _ in self.layer_specs):
            return 0
        if self.window <= 0:
            raise ValueError("local_attn layers need a window > 0")
        return -(-self.window // self.page_size) + 1

    @property
    def is_encoder(self) -> bool:
        return not self.causal

    def param_count(self) -> int:
        """Analytic parameter count (embeddings included once)."""
        n = self.vocab_size * self.d_model
        if not self.tie_embeddings and self.vocab_size:
            n += self.vocab_size * self.d_model
        for mixer, ffn in self.layer_specs:
            if mixer in ("attn", "local_attn"):
                n += self.d_model * (self.num_heads + 2 * self.num_kv_heads) \
                     * self.head_dim
                n += self.num_heads * self.head_dim * self.d_model
            elif mixer == "mamba2":
                di = self.expand * self.d_model
                h = di // self.ssm_head_dim
                n += self.d_model * (2 * di + 2 * self.ssm_state + h)
                n += di * self.d_model
            elif mixer == "rglru":
                w = self.lru_width
                n += 2 * self.d_model * w + 2 * w * w + w * self.d_model
            if ffn == "mlp":
                k = 3 if self.mlp == "swiglu" else 2
                n += k * self.d_model * self.d_ff
            elif ffn == "moe":
                k = 3 if self.mlp == "swiglu" else 2
                n += self.num_experts * k * self.d_model * self.d_ff
                n += self.d_model * self.routed_experts
        return n

    def active_param_count(self) -> int:
        """MoE: params touched per token (router top-k)."""
        if self.family != "moe":
            return self.param_count()
        dense = self.param_count()
        k = 3 if self.mlp == "swiglu" else 2
        per_expert = k * self.d_model * self.d_ff
        n_moe = sum(1 for _, f in self.layer_specs if f == "moe")
        return dense - n_moe * (self.num_experts - self.top_k) * per_expert
