"""Gated short convolution, LFM2's conv mixer (HF ``lfm2`` / ``lfm2_moe``).

    B, C, x' = split3(in_proj(x))              in_proj d -> 3d, no bias
    y = C * conv1d_depthwise_causal(B * x')    K taps, no bias
    out = out_proj(y)                          d -> d, no bias

The convolution is causal: position t sees the gated inputs t-K+1 .. t, and
the first K-1 positions see zero padding (or, when decoding, the cache: the
last K-1 gated inputs of the sequence so far).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import tracing
from repro.models.layers import _dense_init


def conv_init(key, cfg):
    d, k = cfg.d_model, cfg.conv_kernel
    ks = jax.random.split(key, 3)
    params = {
        "in_proj": _dense_init(ks[0], (d, 3 * d)),
        "taps": _dense_init(ks[1], (k, d)),  # [K, d]: taps[K-1] is the current input
        "out_proj": _dense_init(ks[2], (d, d)),
    }
    axes = {
        "in_proj": ("embed", "mlp"),
        "taps": (None, "mlp"),
        "out_proj": ("mlp", "embed"),
    }
    return params, axes


@tracing.scope(tracing.CONV)
def conv_apply(
    params,
    cfg,
    x: jax.Array,  # [B, S, d]
    state: Optional[jax.Array] = None,  # [B, K-1, d]: the last K-1 gated inputs
    update_cache: bool = False,
):
    """Returns (out [B, S, d], new state): ``state`` advanced past ``x``
    where ``update_cache``, else as given."""
    dt = x.dtype
    b, s, d = x.shape
    k = cfg.conv_kernel
    bcx = x @ params["in_proj"].astype(dt)
    gate_b, gate_c, xin = jnp.split(bcx, 3, axis=-1)
    bx = gate_b * xin
    prev = jnp.zeros((b, k - 1, d), dt) if state is None else state.astype(dt)
    window = jnp.concatenate([prev, bx], axis=1)  # [B, S + K - 1, d]
    taps = params["taps"].astype(jnp.float32)
    y = sum(
        window[:, j : j + s].astype(jnp.float32) * taps[j] for j in range(k)
    ).astype(dt)
    out = (gate_c * y) @ params["out_proj"].astype(dt)
    if state is not None and update_cache:
        state = window[:, s:].astype(state.dtype)
    return out, state
