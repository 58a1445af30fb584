"""The composable transformer stack covering every architecture of the zoo.

Each layer has a (mixer, feed-forward) kind (``ModelConfig.layer_kinds``).
The kinds are split into segments, each a period of kinds repeated
(``layer_segments``): LFM2's two dense conv layers, then its (attention,
conv, conv, conv) period; one segment for a pattern cycled from layer 0.
Each segment's layers are stacked per period position and iterated with one
``jax.lax.scan`` (the scan body applies one full period), keeping HLO size
independent of depth — essential for 512-device dry-run compiles.  Stacked
params are a flat tuple over the positions of every segment in order.

Caches: ``ModelCache`` carries, per position, group-stacked KV and/or
conv/SSM state arrays plus one global length counter, so decode steps are a
scan per segment with dynamic-slice writes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import layers as nn
from repro.models.activation_sharding import shard_act
from repro.models import moe as moe_lib
from repro.models import short_conv as conv_lib
from repro.models import ssm as ssm_lib
from repro.models.config import ATTENTION_MIXERS, SSM_MIXERS, ModelConfig


class BlockAux(NamedTuple):
    lb_loss: jax.Array
    z_loss: jax.Array
    expert_load: jax.Array  # most tokens to one expert over the mean (max over layers)


def _zero_aux():
    z = jnp.zeros((), jnp.float32)
    return BlockAux(z, z, z)


def _add_aux(a: BlockAux, b: BlockAux) -> BlockAux:
    return BlockAux(a.lb_loss + b.lb_loss, a.z_loss + b.z_loss,
                    jnp.maximum(a.expert_load, b.expert_load))


# ------------------------------------------------------------------ block ---

def block_init(key, cfg: ModelConfig, kind: tuple, cross: bool = False):
    """``kind``: the layer's (mixer, feed-forward), ``ModelConfig.layer_kinds``."""
    mixer, ffn = kind
    ks = jax.random.split(key, 8)
    params: dict = {"ln1": nn.rmsnorm_init(cfg.d_model)[0],
                    "ln2": nn.rmsnorm_init(cfg.d_model)[0]}
    axes: dict = {"ln1": ("embed_unsharded",), "ln2": ("embed_unsharded",)}
    if mixer in ATTENTION_MIXERS:
        params["attn"], axes["attn"] = attn_lib.attn_init(ks[0], cfg)
    if mixer in SSM_MIXERS:
        params["ssm"], axes["ssm"] = ssm_lib.ssm_init(ks[1], cfg)
    if mixer == "conv":
        params["conv"], axes["conv"] = conv_lib.conv_init(ks[5], cfg)
    if cross:
        params["ln_cross"] = nn.rmsnorm_init(cfg.d_model)[0]
        axes["ln_cross"] = ("embed_unsharded",)
        params["cross"], axes["cross"] = attn_lib.attn_init(ks[2], cfg, cross=True)
    if ffn == "moe":
        params["moe"], axes["moe"] = moe_lib.moe_init(ks[3], cfg)
    if ffn == "mlp" or (ffn == "moe" and cfg.moe.dense_residual):
        params["mlp"], axes["mlp"] = nn.mlp_init(
            ks[4], cfg.d_model, cfg.d_ff, cfg.mlp_type
        )
    return params, axes


def block_apply(
    params,
    cfg: ModelConfig,
    mixer: str,
    x: jax.Array,
    positions: jax.Array,
    kv_cache: Optional[attn_lib.KVCache] = None,
    ssm_cache: Optional[ssm_lib.SSMCache] = None,
    update_cache: bool = False,
    enc_out: Optional[jax.Array] = None,
    causal: bool = True,
):
    """One pre-norm layer.  ``ssm_cache`` carries a mamba/hymba mixer's
    state, or a conv mixer's window in its ``conv`` field."""
    aux = _zero_aux()
    h = nn.rmsnorm(x, params["ln1"], cfg.rmsnorm_eps)
    new_kv, new_ssm = kv_cache, ssm_cache
    mix = jnp.zeros_like(x)
    n_parts = 0
    if mixer == "conv":
        c, conv_state = conv_lib.conv_apply(
            params["conv"], cfg, h,
            state=None if ssm_cache is None else ssm_cache.conv,
            update_cache=update_cache,
        )
        if ssm_cache is not None:
            new_ssm = ssm_lib.SSMCache(conv=conv_state, h=None)
        mix = mix + c
        n_parts += 1
    if mixer in ATTENTION_MIXERS:
        a, new_kv = attn_lib.attn_apply(
            params["attn"], cfg, h, positions,
            "local" if mixer == "local" else "global",
            cache=kv_cache, update_cache=update_cache, causal=causal,
        )
        mix = mix + a
        n_parts += 1
    if mixer in SSM_MIXERS:
        s, new_ssm = ssm_lib.ssm_apply(
            params["ssm"], cfg, h, cache=ssm_cache, update_cache=update_cache
        )
        mix = mix + s
        n_parts += 1
    if n_parts > 1:
        mix = mix / n_parts  # Hymba: mean-fuse parallel attention + SSM heads
    x = x + mix

    if enc_out is not None and "cross" in params:
        hc = nn.rmsnorm(x, params["ln_cross"], cfg.rmsnorm_eps)
        c, _ = attn_lib.attn_apply(
            params["cross"], cfg, hc, positions, "global",
            xk=enc_out, causal=False,
        )
        x = x + c

    h2 = nn.rmsnorm(x, params["ln2"], cfg.rmsnorm_eps)
    ff = jnp.zeros_like(x)
    if "moe" in params:
        mo, moe_aux = moe_lib.moe_apply(params["moe"], cfg, h2)
        ff = ff + mo
        aux = BlockAux(*moe_aux)
    if "mlp" in params:  # a dense layer, or arctic's dense residual
        ff = ff + nn.mlp_apply(params["mlp"], h2, cfg.mlp_type)
    x = x + ff
    return x, new_kv, new_ssm, aux


# ------------------------------------------------------------------ stack ---

@dataclasses.dataclass
class ModelCache:
    """Group-stacked caches per stacked position + one global length."""

    kv_k: tuple  # per position: [G, B, S, KV, D] or None
    kv_v: tuple
    ssm_conv: tuple  # per position: [G, B, W-1, C] (mamba/hymba), [G, B, K-1, d] (conv) or None
    ssm_h: tuple  # per position: [G, B, H, P, N] or None
    length: jax.Array  # [] int32
    enc_out: Optional[jax.Array] = None  # [B, S_enc, d] (enc-dec only)


def _cache_flatten(c: ModelCache):
    return (c.kv_k, c.kv_v, c.ssm_conv, c.ssm_h, c.length, c.enc_out), None


def _cache_unflatten(aux, leaves):
    return ModelCache(*leaves)


jax.tree_util.register_pytree_node(ModelCache, _cache_flatten, _cache_unflatten)


def layer_segments(cfg: ModelConfig, num_layers: int) -> tuple:
    """The first ``num_layers`` layers' kinds as ``((period, repeats), ...)``
    in order, ``period`` a tuple of (mixer, feed-forward) kinds.  From each
    layer on, the period repeated at least twice that covers the most layers
    (the shortest such) starts the next segment; a layer whose kinds repeat
    no period is a segment of its own."""
    kinds = cfg.layer_kinds(num_layers)
    segs, i, n = [], 0, len(kinds)
    while i < n:
        p, r = 1, 1
        for q in range(1, (n - i) // 2 + 1):
            reps = 1
            while kinds[i + reps * q : i + (reps + 1) * q] == kinds[i : i + q]:
                reps += 1
            if reps > 1 and reps * q > r * p:
                p, r = q, reps
        segs.append((kinds[i : i + p], r))
        i += p * r
    return tuple(segs)


def _positions(cfg: ModelConfig, num_layers: int):
    """Each stacked position in order -> (kind, groups)."""
    return [(kind, reps) for kinds, reps in layer_segments(cfg, num_layers)
            for kind in kinds]


def stack_init(key, cfg: ModelConfig, num_layers: int, cross: bool = False):
    """Init the stacked params: a tuple over the stacked positions of every
    segment (``layer_segments``), each a pytree whose leaves carry a leading
    [G] axis, G the segment's repeats."""
    stacked, stacked_axes = [], []
    for pos, (kind, groups) in enumerate(_positions(cfg, num_layers)):
        keys = jax.random.split(jax.random.fold_in(key, pos), groups)
        per_layer = [block_init(k, cfg, kind, cross) for k in keys]
        params = jax.tree.map(lambda *xs: jnp.stack(xs), *[p for p, _ in per_layer])
        axes = jax.tree.map(
            lambda a: ("layers",) + a,
            per_layer[0][1],
            is_leaf=lambda x: isinstance(x, tuple)
            and all(e is None or isinstance(e, str) for e in x),
        )
        stacked.append(params)
        stacked_axes.append(axes)
    return tuple(stacked), tuple(stacked_axes)


def _slice0(stack, idx):
    return jax.tree.map(
        lambda s: jax.lax.squeeze(
            jax.lax.dynamic_slice_in_dim(s, idx, 1, axis=0), (0,)
        ),
        stack,
    )


def _write0(stack, idx, val):
    return jax.tree.map(
        lambda s, v: jax.lax.dynamic_update_slice_in_dim(
            s, v[None].astype(s.dtype), idx, axis=0
        ),
        stack, val,
    )


def _scan_segment(
    seg_params, cfg, kinds, groups, x, positions, caches, length,
    update_cache, enc_out, causal,
):
    """One segment: ``groups`` repeats of the period ``kinds`` as one
    ``lax.scan``.  ``caches`` is (kv_k, kv_v, ssm_conv, ssm_h), each a tuple
    over the period's positions (None entries where a position has none),
    or None.  Returns (x, caches, aux)."""
    period = len(kinds)
    has_cache = caches is not None

    # Cache stacks ride in the scan CARRY (updated in place with dynamic
    # slices at the group index) rather than as xs->ys — scan cannot alias
    # xs buffers to ys buffers, which would double-buffer multi-GiB KV
    # caches at decode (EXPERIMENTS.md §Perf iteration 1).
    def body(carry, params_slices):
        xc, g_idx, kv_k, kv_v, ssm_conv, ssm_h = carry
        xc = shard_act(xc, "batch", "seq", "act_embed")
        aux_tot = _zero_aux()
        for pos in range(period):
            mixer = kinds[pos][0]
            kv_c = None
            if has_cache and kv_k[pos] is not None:
                kv_c = attn_lib.KVCache(
                    k=_slice0(kv_k[pos], g_idx),
                    v=_slice0(kv_v[pos], g_idx),
                    length=length,
                )
            ssm_c = None
            if has_cache and ssm_conv[pos] is not None:
                ssm_c = ssm_lib.SSMCache(
                    conv=_slice0(ssm_conv[pos], g_idx),
                    h=_slice0(ssm_h[pos], g_idx),
                )
            xc, nkv, nssm, aux = block_apply(
                params_slices[pos], cfg, mixer, xc, positions,
                kv_cache=kv_c, ssm_cache=ssm_c, update_cache=update_cache,
                enc_out=enc_out, causal=causal,
            )
            if has_cache and nkv is not None and update_cache:
                kv_k = kv_k[:pos] + (_write0(kv_k[pos], g_idx, nkv.k),) + kv_k[pos + 1:]
                kv_v = kv_v[:pos] + (_write0(kv_v[pos], g_idx, nkv.v),) + kv_v[pos + 1:]
            if has_cache and nssm is not None and update_cache:
                ssm_conv = (
                    ssm_conv[:pos]
                    + (_write0(ssm_conv[pos], g_idx, nssm.conv),)
                    + ssm_conv[pos + 1:]
                )
                ssm_h = (
                    ssm_h[:pos] + (_write0(ssm_h[pos], g_idx, nssm.h),) + ssm_h[pos + 1:]
                )
            aux_tot = _add_aux(aux_tot, aux)
        return (xc, g_idx + 1, kv_k, kv_v, ssm_conv, ssm_h), aux_tot

    # remat only matters under grad (training); at serve time the checkpoint
    # barriers would also block in-place carry updates of the KV stacks.
    body_fn = jax.checkpoint(body) if (cfg.remat and not update_cache) else body
    stacks = caches if has_cache else ((None,) * period,) * 4
    carry0 = (x, jnp.zeros((), jnp.int32)) + tuple(stacks)
    (x, _, *stacks), auxs = jax.lax.scan(
        body_fn, carry0, tuple(seg_params), length=groups
    )
    aux = BlockAux(jnp.sum(auxs.lb_loss), jnp.sum(auxs.z_loss),
                   jnp.max(auxs.expert_load))
    return x, (tuple(stacks) if has_cache else None), aux


def stack_apply(
    stacked_params,
    cfg: ModelConfig,
    x: jax.Array,
    positions: jax.Array,
    num_layers: int,
    cache: Optional[ModelCache] = None,
    update_cache: bool = False,
    enc_out: Optional[jax.Array] = None,
    causal: bool = True,
):
    """Run the stack, one scan per segment. Returns (x, new_cache, aux)."""
    has_cache = cache is not None
    stacks = (
        [cache.kv_k, cache.kv_v, cache.ssm_conv, cache.ssm_h] if has_cache else None
    )
    aux = _zero_aux()
    base = 0
    for kinds, groups in layer_segments(cfg, num_layers):
        sl = slice(base, base + len(kinds))
        seg_caches = tuple(st[sl] for st in stacks) if has_cache else None
        x, seg_caches, seg_aux = _scan_segment(
            stacked_params[sl], cfg, kinds, groups, x, positions, seg_caches,
            cache.length if has_cache else None, update_cache, enc_out, causal,
        )
        if has_cache:
            stacks = [st[: sl.start] + new + st[sl.stop:]
                      for st, new in zip(stacks, seg_caches)]
        aux = _add_aux(aux, seg_aux)
        base = sl.stop

    new_cache = None
    if has_cache:
        new_len = cache.length + (x.shape[1] if update_cache else 0)
        new_cache = ModelCache(
            kv_k=stacks[0], kv_v=stacks[1], ssm_conv=stacks[2], ssm_h=stacks[3],
            length=new_len, enc_out=cache.enc_out,
        )
    return x, new_cache, aux


def init_model_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype,
    num_layers: Optional[int] = None, enc_out: Optional[jax.Array] = None,
) -> ModelCache:
    nl = num_layers or cfg.num_layers
    kv_k, kv_v, ssm_conv, ssm_h = [], [], [], []
    s = cfg.ssm
    for (mixer, _), groups in _positions(cfg, nl):
        if mixer in ATTENTION_MIXERS:
            shape = (groups, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
            kv_k.append(jnp.zeros(shape, dtype))
            kv_v.append(jnp.zeros(shape, dtype))
        else:
            kv_k.append(None)
            kv_v.append(None)
        if mixer in SSM_MIXERS:
            di = s.d_inner(cfg.d_model)
            nh = s.num_heads(cfg.d_model)
            ssm_conv.append(
                jnp.zeros((groups, batch, s.conv_width - 1, di + 2 * s.state_dim), dtype)
            )
            ssm_h.append(
                jnp.zeros((groups, batch, nh, s.head_dim, s.state_dim), jnp.float32)
            )
        elif mixer == "conv":
            ssm_conv.append(
                jnp.zeros((groups, batch, cfg.conv_kernel - 1, cfg.d_model), dtype)
            )
            ssm_h.append(None)
        else:
            ssm_conv.append(None)
            ssm_h.append(None)
    return ModelCache(
        kv_k=tuple(kv_k), kv_v=tuple(kv_v),
        ssm_conv=tuple(ssm_conv), ssm_h=tuple(ssm_h),
        length=jnp.zeros((), jnp.int32), enc_out=enc_out,
    )


def model_cache_axes(cfg: ModelConfig, shard_kv_seq: bool = False) -> ModelCache:
    """Logical axes matching init_model_cache's pytree."""
    kv_ax = ("layers", "batch", "kv_seq" if shard_kv_seq else None, "kv_heads", "head_dim")
    conv_ax = ("layers", "batch", None, "ssm_inner")
    h_ax = ("layers", "batch", "ssm_heads", None, "state")
    kv_k, kv_v, ssm_conv, ssm_h = [], [], [], []
    for (mixer, _), _ in _positions(cfg, cfg.num_layers):
        att = mixer in ATTENTION_MIXERS
        ssm = mixer in SSM_MIXERS
        kv_k.append(kv_ax if att else None)
        kv_v.append(kv_ax if att else None)
        ssm_conv.append(conv_ax if ssm or mixer == "conv" else None)
        ssm_h.append(h_ax if ssm else None)
    return ModelCache(
        kv_k=tuple(kv_k), kv_v=tuple(kv_v),
        ssm_conv=tuple(ssm_conv), ssm_h=tuple(ssm_h),
        length=(),
        enc_out=("batch", None, "act_embed") if cfg.encoder is not None else None,
    )
