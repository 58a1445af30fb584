"""Plain reference of the model tagging level: a Qwen3 trunk with a
per-predicate head, in numpy float32.

Published architecture (Qwen3-1.7B ``config.json``): pre-norm decoder layers
with RMSNorm (eps 1e-6); grouped-query attention (16 query heads, 8 KV
heads, head_dim 128) with RMSNorm on each query and key head before rotary
embedding (theta 1e6, rotate-half); a SwiGLU MLP (intermediate 6144).  The
tagging head projects an object's feature vector into the model width,
repeats it over ``positions`` positions, runs the trunk with full
(non-causal) attention over them, mean-pools the last hidden states and
applies a sigmoid to one logit.  Departures from the published model: no
token embedding and no final norm or LM head (the head replaces them).

Weights are read as data (the benchmark's own, made from the seed); every
product is computed in float32 from them.  ``quantize`` and
``quantize_fp8`` are the steps below the configuration's bfloat16 weights:
symmetric int8, or float8 e4m3, each scaled per output channel.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def rmsnorm(x, w, eps):
    x = x.astype(np.float32)
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [B, T, H, D] -> rotate-half rotary embedding at positions 0..T-1."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * freqs[None, :]
    sin, cos = np.sin(ang)[None, :, None, :], np.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def silu(x):
    return x / (1.0 + np.exp(-x))


def quantize(w, n_in: int = 1):
    """Symmetric int8 per output channel (the leading ``n_in`` axes are the
    matmul's input), returned dequantised as float32."""
    w = np.asarray(w, np.float32)
    w2 = w.reshape(int(np.prod(w.shape[:n_in])), -1)
    scale = np.max(np.abs(w2), axis=0, keepdims=True) / 127.0
    scale = np.where(scale > 0, scale, 1.0)
    return (np.clip(np.round(w2 / scale), -127, 127) * scale).reshape(w.shape)


def quantize_fp8(w, n_in: int = 1):
    """float8 e4m3 per output channel (largest magnitude scaled to 448, the
    format's largest), returned dequantised as float32."""
    w = np.asarray(w, np.float32)
    w2 = w.reshape(int(np.prod(w.shape[:n_in])), -1)
    scale = np.max(np.abs(w2), axis=0, keepdims=True) / 448.0
    scale = np.where(scale > 0, scale, 1.0)
    return ((w2 / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float32) * scale).reshape(w.shape)


def layer(x, p: dict, arch: dict):
    """One decoder layer.  ``p`` holds float32 weights: ln1, ln2 [d]; wq
    [d, H, hd], wk, wv [d, KV, hd], wo [H, hd, d], q_norm, k_norm [hd];
    wg, wu [d, ff], wd [ff, d]."""
    eps = arch["rms_norm_eps"]
    b, t, d = x.shape

    def proj(h, w):  # [B, T, d] x [d, heads, hd] -> [B, T, heads, hd]
        return (h.reshape(b * t, d) @ w.reshape(d, -1)).reshape(b, t, w.shape[1], w.shape[2])

    h = rmsnorm(x, p["ln1"], eps)
    q, k, v = proj(h, p["wq"]), proj(h, p["wk"]), proj(h, p["wv"])
    q = rope(rmsnorm(q, p["q_norm"], eps), arch["rope_theta"])
    k = rope(rmsnorm(k, p["k_norm"], eps), arch["rope_theta"])
    group = q.shape[2] // k.shape[2]
    k = np.repeat(k, group, axis=2)
    v = np.repeat(v, group, axis=2)
    s = np.einsum("bqhk,bshk->bhqs", q, k) / np.sqrt(q.shape[-1])
    s = np.exp(s - s.max(axis=-1, keepdims=True))
    a = s / s.sum(axis=-1, keepdims=True)
    o = np.einsum("bhqs,bshk->bqhk", a, v)
    x = x + (o.reshape(b * t, -1) @ p["wo"].reshape(-1, d)).reshape(b, t, d)
    h2 = rmsnorm(x, p["ln2"], eps)
    return x + (silu(h2 @ p["wg"]) * (h2 @ p["wu"])) @ p["wd"]


def tag(feats, proj, out, layers, arch: dict, positions: int):
    """Probabilities [B] of the model level for B objects: features
    ``feats`` [B, F], each object's predicate head ``proj`` [B, F, d] and
    ``out`` [B, d].  ``layers`` yields each layer's float32 weights in
    order."""
    x = np.einsum("bf,bfd->bd", np.asarray(feats, np.float32), np.asarray(proj, np.float32))
    x = np.repeat(x[:, None, :], positions, axis=1)
    for p in layers:
        x = layer(x, p, arch)
    logit = np.einsum("bd,bd->b", x.mean(axis=1), np.asarray(out, np.float32))
    return 1.0 / (1.0 + np.exp(-logit))
