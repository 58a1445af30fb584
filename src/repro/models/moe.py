"""Mixture-of-Experts layer, dropless (grok-1: 8e top-2; arctic: 128e top-2 +
dense residual; LFM2-24B-A2B: 64e top-4, sigmoid router with a selection
bias).

Every token of the call goes to each of its top-k experts; none is ever
dropped.  Dispatch works over all the call's tokens at once, flattened
across sequences: the (token, expert) pairs are sorted by expert, the
gathered rows run through grouped matmuls (``jax.lax.ragged_dot``, one group
per expert, group sizes counted from the routing), and the outputs return
through the inverse permutation, weighted and summed over each token's k
experts.  Work is proportional to tokens x k, whatever the routing.  Where
the launcher shards tokens over data-parallel devices, each device sorts
and multiplies its own tokens (a ``shard_map`` over those mesh axes), so no
token row crosses devices.

The router follows the config: softmax top-k, or (LFM2) ``s = sigmoid(x W)``
with experts chosen by top-k of ``s + expert_bias`` and weighted by ``s`` at
the chosen experts.  Weights are divided by their sum (plus 1e-6 under the
sigmoid router, as HF ``lfm2_moe``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import tracing
from repro.models.activation_sharding import mesh_axes_of, shard_act
from repro.models.layers import _dense_init


class MoEAux(NamedTuple):
    load_balance_loss: jax.Array
    router_z_loss: jax.Array
    expert_load: jax.Array  # most tokens routed to one expert, over the mean


def moe_init(key, cfg):
    m = cfg.moe
    d, f = cfg.d_model, m.d_ff_expert
    ks = jax.random.split(key, 5)
    swiglu = cfg.mlp_type in ("swiglu", "geglu")
    params = {
        "router": _dense_init(ks[0], (d, m.num_experts)),
        "wu": _dense_init(ks[1], (m.num_experts, d, f), in_axis=1),
        "wd": _dense_init(ks[2], (m.num_experts, f, d), in_axis=1),
    }
    axes = {
        "router": ("embed", None),
        "wu": ("experts", "expert_embed", "mlp"),
        "wd": ("experts", "mlp", "expert_embed"),
    }
    if swiglu:
        params["wg"] = _dense_init(ks[3], (m.num_experts, d, f), in_axis=1)
        axes["wg"] = ("experts", "expert_embed", "mlp")
    if m.router == "sigmoid":
        params["expert_bias"] = jnp.zeros((m.num_experts,), jnp.float32)
        axes["expert_bias"] = (None,)
    return params, axes


def route(params, cfg, x: jax.Array):
    """x: [T, d] -> (expert ids [T, k], weights [T, k] f32, router logits
    [T, E] f32).  Logits are computed in float32."""
    m = cfg.moe
    logits = jnp.dot(
        x.astype(jnp.float32), params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    if m.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + params["expert_bias"], m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        eps = 1e-6
    elif m.router == "softmax":
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
        eps = 0.0
    else:
        raise ValueError(f"unknown router {m.router!r}")
    return idx, w / jnp.maximum(w.sum(-1, keepdims=True) + eps, 1e-9), logits


def _dispatch(cfg, experts, xf, idx, w):
    """Tokens xf [T, d] through their experts idx [T, k] with weights w
    [T, k] -> (y [T, d], rows per expert [E])."""
    dt = xf.dtype
    t, k = idx.shape
    # sort the t*k (token, expert) pairs by expert; stable, so each expert's
    # rows keep token order
    flat = idx.reshape(t * k)
    order = jnp.argsort(flat, stable=True)
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(t * k, dtype=order.dtype))
    sizes = jnp.bincount(flat, length=cfg.moe.num_experts).astype(jnp.int32)
    xs = xf[order // k]  # [t*k, d]

    def gmm(lhs, rhs):
        return jax.lax.ragged_dot(lhs, rhs.astype(dt), sizes)

    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        h = act(gmm(xs, experts["wg"])) * gmm(xs, experts["wu"])
    else:
        h = gmm(xs, experts["wu"])
        h = (
            jnp.square(jax.nn.relu(h))
            if cfg.mlp_type == "squared_relu"
            else jax.nn.gelu(h)
        )
    out = gmm(h, experts["wd"])[inv].reshape(t, k, -1)  # back in (token, k) order
    return jnp.einsum("tkd,tk->td", out.astype(jnp.float32), w).astype(dt), sizes


@tracing.scope(tracing.EXPERTS)
def moe_apply(params, cfg, x: jax.Array) -> tuple[jax.Array, MoEAux]:
    """x: [B, S, d] -> (y, aux)."""
    m = cfg.moe
    b, s, d = x.shape
    t, k, e = b * s, m.top_k, m.num_experts
    xf = shard_act(x.reshape(t, d), "batch", "act_embed")
    idx, w, logits = route(params, cfg, xf)
    experts = {n: params[n] for n in ("wg", "wu", "wd") if n in params}

    dispatch = functools.partial(_dispatch, cfg)
    shards = mesh_axes_of("batch")
    if shards is not None:  # each data-parallel device dispatches its own tokens
        mesh, axes = shards
        rows = P(axes)
        # check_vma off: the casts it adds to the replicated weights abort
        # XLA's compile ("Invalid binary instruction opcode copy")
        dispatch = jax.shard_map(
            dispatch, mesh=mesh, in_specs=(P(), rows, rows, rows),
            out_specs=(rows, rows), axis_names=set(axes), check_vma=False,
        )
    y, sizes = dispatch(experts, xf, idx, w)
    sizes = sizes.reshape(-1, e).sum(0)  # summed over the devices' shards

    # aux: Switch-style losses and the load of the busiest expert
    probs = jax.nn.softmax(logits, axis=-1)
    lb = e * jnp.sum(jnp.mean(probs, axis=0) * (sizes / t))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    load = jnp.max(sizes).astype(jnp.float32) * e / (t * k)
    return y.reshape(b, s, d), MoEAux(lb, z, load)
