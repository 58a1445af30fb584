"""Model configuration for the enrichment-model zoo (assigned architectures).

One composable decoder/enc-dec transformer family covers every architecture
of the zoo; every architectural lever is a config field.  Layer mixers are
described by a per-layer pattern cycled across depth, or by an explicit
per-layer list (``layer_types``):

    "global"  — full (causal) GQA attention
    "local"   — sliding-window GQA attention (window = sliding_window)
    "mamba"   — Mamba-2 SSD mixer (attention-free)
    "hymba"   — parallel attention ∥ Mamba-2 heads (Hymba)
    "conv"    — gated short convolution (LFM2; kernel = conv_kernel)

MLPs: "swiglu" | "squared_relu" | "gelu" | "none" (mamba2 has no MLP).  Each
layer's feed-forward is "mlp" (width d_ff), "moe" or "none":
``layer_kinds`` gives every layer's (mixer, feed-forward) pair.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax.numpy as jnp


ATTENTION_MIXERS = ("global", "local", "hymba")
SSM_MIXERS = ("mamba", "hymba")


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Token-choice experts, dropless.  ``router`` "softmax": top-k of the
    softmax; "sigmoid" (LFM2): top-k of sigmoid scores plus a per-expert
    bias that only chooses.  The chosen scores are divided by their sum."""

    num_experts: int
    top_k: int = 2
    d_ff_expert: int = 0  # per-expert hidden size
    dense_residual: bool = False  # Arctic: dense FFN in parallel with MoE
    router: str = "softmax"  # "softmax" | "sigmoid" (with a selection bias [E])
    router_z_loss: float = 1e-3
    load_balance_loss: float = 1e-2


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk_size: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def num_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Encoder tower for enc-dec archs (seamless).  Frontend is a stub:
    inputs are precomputed frame embeddings [B, S_enc, d_model]."""

    num_layers: int = 24
    seq_len: int = 1024  # default encoder length (audio frames)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    mlp_type: str = "swiglu"
    layer_pattern: tuple = ("global",)  # cycled over layers
    layer_types: Optional[tuple] = None  # explicit mixer per layer (wins over the pattern)
    num_dense_layers: int = 0  # leading layers with a d_ff MLP in an MoE model
    conv_kernel: int = 3  # "conv" mixer taps (LFM2 conv_L_cache)
    sliding_window: Optional[int] = None
    qk_norm: bool = False
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    rope_theta: float = 10000.0
    rmsnorm_eps: float = 1e-6
    tie_embeddings: bool = True
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    # modality frontend stub: "text" | "audio" (enc-dec frames) | "vision"
    frontend: str = "text"
    num_image_tokens: int = 0  # vision stub: prefix patch-embedding tokens
    dtype: str = "bfloat16"
    remat: bool = True
    scan_layers: bool = True
    attn_impl: str = "auto"  # "auto" | "dense" | "chunked" | "pallas"
    # long-context capability flag (DESIGN.md §Arch-applicability)
    subquadratic: bool = False

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    def mixer_of_layer(self, i: int) -> str:
        if self.layer_types is not None:
            return self.layer_types[i]
        return self.layer_pattern[i % len(self.layer_pattern)]

    def ffn_of_layer(self, i: int) -> str:
        if self.moe is not None and i >= self.num_dense_layers:
            return "moe"
        return "none" if self.mlp_type == "none" or self.d_ff == 0 else "mlp"

    def layer_kinds(self, num_layers: Optional[int] = None) -> tuple:
        """(mixer, feed-forward) of each of the first ``num_layers`` layers."""
        n = self.num_layers if num_layers is None else num_layers
        return tuple((self.mixer_of_layer(i), self.ffn_of_layer(i)) for i in range(n))

    @property
    def q_per_kv(self) -> int:
        assert self.num_heads % max(self.num_kv_heads, 1) == 0
        return self.num_heads // max(self.num_kv_heads, 1)

    # ---- parameter counting (for roofline MODEL_FLOPS and Table-1 costs) ----

    def _attn_params(self) -> int:
        qkv = self.d_model * self.head_dim * (self.num_heads + 2 * self.num_kv_heads)
        out = self.num_heads * self.head_dim * self.d_model
        return qkv + out

    def _mlp_params(self) -> int:
        if self.mlp_type == "none" or self.d_ff == 0:
            return 0
        mult = 3 if self.mlp_type in ("swiglu", "geglu") else 2
        return mult * self.d_model * self.d_ff

    def _moe_params(self) -> tuple[int, int]:
        """(total, active per token)."""
        if self.moe is None:
            return 0, 0
        m = self.moe
        mult = 3 if self.mlp_type in ("swiglu", "geglu") else 2
        per_expert = mult * self.d_model * m.d_ff_expert
        router = self.d_model * m.num_experts
        total = m.num_experts * per_expert + router
        active = m.top_k * per_expert + router
        if m.dense_residual:
            dense = mult * self.d_model * self.d_ff
            total += dense
            active += dense
        return total, active

    def _conv_params(self) -> int:
        """in_proj d -> 3d (B, C, x), depthwise taps, out_proj; no biases."""
        d = self.d_model
        return 3 * d * d + self.conv_kernel * d + d * d

    def _ssm_params(self) -> int:
        if self.ssm is None:
            return 0
        s = self.ssm
        di = s.d_inner(self.d_model)
        nh = s.num_heads(self.d_model)
        # single-group (G=1) B/C as in repro.models.ssm
        in_proj = self.d_model * (2 * di + 2 * s.state_dim + nh)
        conv = s.conv_width * (di + 2 * s.state_dim)
        out_proj = di * self.d_model
        return in_proj + conv + out_proj + di + 2 * nh  # + norms/D/A/dt_bias

    def param_counts(self) -> dict:
        """Returns dict(total=..., active=...) parameter counts (no embeddings
        double count; embeddings included once)."""
        embed = self.vocab_size * self.d_model
        unembed = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        total = embed + unembed
        active = embed + unembed
        enc_layers = self.encoder.num_layers if self.encoder else 0
        for mixer, ffn in self.layer_kinds():
            layer_t = layer_a = 0
            if mixer in ATTENTION_MIXERS:
                layer_t += self._attn_params()
            if mixer in SSM_MIXERS:
                layer_t += self._ssm_params()
            if mixer == "conv":
                layer_t += self._conv_params()
            layer_a = layer_t
            if ffn == "moe":
                mt, ma = self._moe_params()
                layer_t += mt
                layer_a += ma
            elif ffn == "mlp":
                layer_t += self._mlp_params()
                layer_a += self._mlp_params()
            total += layer_t
            active += layer_a
        for _ in range(enc_layers):
            lt = self._attn_params() + self._mlp_params()
            total += lt
            active += lt
            # decoder cross-attention params
            total += self._attn_params()
            active += self._attn_params()
        return dict(total=total, active=active)

    def model_flops_per_token(self, training: bool = True) -> float:
        """6·N_active per token (2·N fwd, 4·N bwd) for roofline §Roofline."""
        n_active = self.param_counts()["active"]
        mult = 6.0 if training else 2.0
        return mult * n_active


_REGISTRY: dict = {}


def register(cfg_fn):
    """configs/<arch>.py modules register a full() and smoke() pair."""
    _REGISTRY[cfg_fn.__name__] = cfg_fn
    return cfg_fn


def registry() -> dict:
    return dict(_REGISTRY)
