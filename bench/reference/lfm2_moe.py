"""Plain reference of the model tagging level: an LFM2-MoE trunk with a
per-predicate head, in numpy float32.

Published architecture (LFM2-24B-A2B ``config.json``, HF ``lfm2_moe``):
pre-norm decoder layers, ``h = x + op(operator_norm(x))``, then
``out = h + ffn(ffn_norm(h))``, RMSNorm (eps 1e-5, weight times the
normalised input) throughout.  Two kinds of operator:

* gated short convolution: ``B, C, x' = split3(in_proj(x))`` (d -> 3d, no
  bias), ``y = C * conv(B * x')`` with a depthwise causal convolution of
  ``conv_L_cache`` = 3 taps and no bias, then ``out_proj`` (d -> d);
* grouped-query attention (32 query heads, 8 KV heads, head_dim 64) with
  RMSNorm on each query and key head before rotary embedding (theta 1e6,
  rotate-half).

and two kinds of feed-forward: a SwiGLU MLP (the ``num_dense_layers``
leading layers, width 11,776), and a mixture of 64 SwiGLU experts (width
1,536): ``s = sigmoid(x W_router)``, the experts are the top 4 of
``s + expert_bias`` (the bias only chooses), their weights ``s`` at the
chosen experts over their sum plus 1e-6, times ``routed_scaling_factor``;
``y = sum_k w_k SwiGLU_k(x)``.  Every routed (token, expert) pair is
computed, expert by expert over the tokens routed to it (as HF's eager
loop does), and nothing else.

The tagging head projects an object's feature vector into the model width,
repeats it over ``positions`` positions, runs the layers, mean-pools the
last hidden states and applies a sigmoid to one logit.  Departures from the
published model: no token embedding, final norm or LM head (the head
replaces them); positions tiled from one projected feature vector;
attention non-causal over those positions (the convolution stays causal, as
published: its padding is part of the operator).

Weights are read as data (the benchmark's own, made from the seed); every
product is computed in float32 from them.  ``quantize`` and ``quantize_fp8``
(``bench/reference/qwen3``) are the steps below the configuration's bfloat16
weights.
"""

from __future__ import annotations

import numpy as np

from bench.reference.qwen3 import quantize, quantize_fp8, rmsnorm, rope, silu  # noqa: F401


def short_conv(h, p: dict):
    """Gated short convolution of h [B, T, d]: ``in_proj`` [d, 3d], ``taps``
    [K, d] (taps[K-1] weighs the current position), ``out_proj`` [d, d]."""
    b, t, d = h.shape
    bg, cg, xin = np.split(h @ p["in_proj"], 3, axis=-1)
    bx = bg * xin
    k = p["taps"].shape[0]
    y = np.zeros_like(bx)
    for s in range(t):
        for j in range(k):
            src = s - (k - 1) + j
            if src >= 0:
                y[:, s] += bx[:, src] * p["taps"][j]
    return (cg * y) @ p["out_proj"]


def attention(h, p: dict, arch: dict):
    """Non-causal GQA over h [B, T, d]: wq [d, H, hd], wk, wv [d, KV, hd],
    wo [H, hd, d], q_norm, k_norm [hd]."""
    eps = arch["norm_eps"]
    b, t, d = h.shape

    def proj(w):
        return (h.reshape(b * t, d) @ w.reshape(d, -1)).reshape(b, t, w.shape[1], w.shape[2])

    q, k, v = proj(p["wq"]), proj(p["wk"]), proj(p["wv"])
    q = rope(rmsnorm(q, p["q_norm"], eps), arch["rope_theta"])
    k = rope(rmsnorm(k, p["k_norm"], eps), arch["rope_theta"])
    group = q.shape[2] // k.shape[2]
    k = np.repeat(k, group, axis=2)
    v = np.repeat(v, group, axis=2)
    s = np.einsum("bqhk,bshk->bhqs", q, k) / np.sqrt(q.shape[-1])
    s = np.exp(s - s.max(axis=-1, keepdims=True))
    a = s / s.sum(axis=-1, keepdims=True)
    o = np.einsum("bhqs,bshk->bqhk", a, v)
    return (o.reshape(b * t, -1) @ p["wo"].reshape(-1, d)).reshape(b, t, d)


def route(x, router, bias, top_k: int, scale: float):
    """x [N, d] -> (experts [N, k], weights [N, k]): top-k of sigmoid scores
    plus the selection bias, weighted by the scores alone."""
    s = 1.0 / (1.0 + np.exp(-(x @ router)))
    chosen = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :top_k]
    w = np.take_along_axis(s, chosen, axis=-1)
    w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return chosen, w * scale


def experts(x, p: dict, arch: dict, routes: list | None = None):
    """Mixture of experts over x [N, d]: router [d, E], expert_bias [E], wg,
    wu [E, d, f], wd [E, f, d].  ``routes``, where given, receives the
    chosen experts."""
    chosen, w = route(x, p["router"], p["expert_bias"], arch["num_experts_per_tok"],
                      arch["routed_scaling_factor"])
    if routes is not None:
        routes.append(chosen)
    y = np.zeros_like(x)
    for e in np.unique(chosen):
        tok, slot = np.nonzero(chosen == e)
        xe = x[tok]
        hidden = silu(xe @ p["wg"][e]) * (xe @ p["wu"][e])
        np.add.at(y, tok, w[tok, slot][:, None] * (hidden @ p["wd"][e]))
    return y


def layer(x, p: dict, arch: dict, routes: list | None = None):
    """One decoder layer; ``p["mixer"]`` is "conv" or "attention" and
    ``p["ffn"]`` "mlp" or "moe", beside their float32 weights and the norms
    ``ln1`` (operator_norm) and ``ln2`` (ffn_norm) [d]."""
    eps = arch["norm_eps"]
    h = rmsnorm(x, p["ln1"], eps)
    x = x + (short_conv(h, p) if p["mixer"] == "conv" else attention(h, p, arch))
    h2 = rmsnorm(x, p["ln2"], eps)
    b, t, d = x.shape
    if p["ffn"] == "mlp":
        return x + (silu(h2 @ p["wg"]) * (h2 @ p["wu"])) @ p["wd"]
    return x + experts(h2.reshape(b * t, d), p, arch, routes).reshape(b, t, d)


def tag(feats, proj, out, layers, arch: dict, positions: int, routes: list | None = None):
    """Probabilities [B] of the model level for B objects: features
    ``feats`` [B, F], each object's predicate head ``proj`` [B, F, d] and
    ``out`` [B, d].  ``layers`` yields each layer's float32 weights in
    order.  ``routes``, where given, receives each expert layer's chosen
    experts [B * positions, k]."""
    x = np.einsum("bf,bfd->bd", np.asarray(feats, np.float32), np.asarray(proj, np.float32))
    x = np.repeat(x[:, None, :], positions, axis=1)
    for p in layers:
        x = layer(x, p, arch, routes)
    logit = np.einsum("bd,bd->b", x.mean(axis=1), np.asarray(out, np.float32))
    return 1.0 / (1.0 + np.exp(-logit))
