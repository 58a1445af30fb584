"""LFM2-MoE against its plain float32 reference (``bench/reference/
lfm2_moe.py``) at the smoke size, on seeded random weights: the gated short
conv, the sigmoid router, the dropless expert dispatch (also under a router
skewed so that every token picks the same experts) and the whole trunk with
its tagging head; plus the stack's layer segments.  The same dispatch under
the softmax router (grok-1's smoke twin, GeGLU experts) is held to a plain
loop over each token's top-k experts in this file.

Tolerance: 2e-5 absolute, everything in float32 under
``jax.default_matmul_precision("highest")`` on both sides; what is left is
the order of float32 sums (observed below 5e-6).  A bfloat16 step anywhere
moves the outputs by more than 1e-3.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.reference import lfm2_moe as ref  # noqa: E402
from repro.configs.archs import get_config  # noqa: E402
from repro.enrich import cascade  # noqa: E402
from repro.models import moe, short_conv  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

ATOL = 2e-5
CASES = ["conv", "router", "router_skewed", "experts", "experts_skewed", "trunk",
         "experts_softmax", "experts_softmax_skewed"]


def _cfg():
    return dataclasses.replace(get_config("lfm2-24b-a2b", smoke=True), dtype="float32")


def _arch(cfg):
    return dict(norm_eps=cfg.rmsnorm_eps, rope_theta=cfg.rope_theta,
                num_experts_per_tok=cfg.moe.top_k,
                routed_scaling_factor=1.0)


def _np(tree):
    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def _moe_params(cfg, skewed: bool):
    p, _ = moe.moe_init(jax.random.PRNGKey(3), cfg)
    bias = jax.random.normal(jax.random.PRNGKey(4), (cfg.moe.num_experts,)) * 0.05
    if skewed:  # experts 1 and 5 win every token's choice
        bias = bias.at[jnp.asarray([1, 5])].set(10.0)
    return dict(p, expert_bias=bias)


def _softmax_experts(x, p: dict, top_k: int):
    """Token by token: softmax router, top-k weights over their sum, GeGLU
    experts (tanh GELU, as ``jax.nn.gelu``)."""
    def gelu(v):
        return 0.5 * v * (1.0 + np.tanh(np.sqrt(2.0 / np.pi) * (v + 0.044715 * v ** 3)))

    logits = x @ p["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    y = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:top_k]
        w = probs[t, top] / probs[t, top].sum()
        for e, we in zip(top, w):
            y[t] += we * (gelu(x[t] @ p["wg"][e]) * (x[t] @ p["wu"][e])) @ p["wd"][e]
    return y


def _ref_layers(cfg, layers):
    """The program's stacked trunk -> the reference's per-layer dicts."""
    out, base = [], 0
    for kinds, groups in tf.layer_segments(cfg, cfg.num_layers):
        for g in range(groups):
            for j, (mixer, ffn) in enumerate(kinds):
                lp = _np(jax.tree.map(lambda x: x[g], layers[base + j]))
                d = dict(mixer="conv" if mixer == "conv" else "attention", ffn=ffn,
                         ln1=lp["ln1"], ln2=lp["ln2"])
                d.update(lp.get("conv", {}), **lp.get("attn", {}))
                d.update(lp.get("mlp", {}) if ffn == "mlp" else lp["moe"])
                out.append(d)
        base += len(kinds)
    return out


@pytest.mark.parametrize("case", CASES)
def test_layer_matches_reference(case):
    cfg = _cfg()
    arch = _arch(cfg)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 8, cfg.d_model)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        if case == "conv":
            p, _ = short_conv.conv_init(jax.random.PRNGKey(1), cfg)
            got, _ = short_conv.conv_apply(p, cfg, jnp.asarray(x))
            want = ref.short_conv(x, _np(p))
        elif case.startswith("router"):
            p = _moe_params(cfg, case.endswith("skewed"))
            xf = x.reshape(-1, cfg.d_model)
            idx, w, _ = moe.route(p, cfg, jnp.asarray(xf))
            want_idx, want = ref.route(xf, *[np.asarray(p[k], np.float32)
                                             for k in ("router", "expert_bias")],
                                       cfg.moe.top_k, 1.0)
            np.testing.assert_array_equal(np.asarray(idx), want_idx)
            if case.endswith("skewed"):
                assert np.all(np.sort(want_idx, axis=-1) == [1, 5])
            got = w
        elif case.startswith("experts_softmax"):
            cfg = dataclasses.replace(get_config("grok-1-314b", smoke=True), dtype="float32")
            p, _ = moe.moe_init(jax.random.PRNGKey(6), cfg)
            if case.endswith("skewed"):  # experts 0 and 1 win every token
                x = np.abs(x)
                p = dict(p, router=jnp.concatenate(
                    [jnp.ones((cfg.d_model, 2)), -jnp.ones((cfg.d_model, 2))], axis=1))
            got, aux = moe.moe_apply(p, cfg, jnp.asarray(x))
            want = _softmax_experts(x.reshape(-1, cfg.d_model), _np(p),
                                    cfg.moe.top_k).reshape(x.shape)
            load = cfg.moe.num_experts / cfg.moe.top_k
            assert (float(aux.expert_load) == load) == case.endswith("skewed")
        elif case.startswith("experts"):
            p = _moe_params(cfg, case.endswith("skewed"))
            got, aux = moe.moe_apply(p, cfg, jnp.asarray(x))
            want = ref.experts(x.reshape(-1, cfg.d_model), _np(p), arch).reshape(x.shape)
            # nothing dropped: the busiest expert holds every token when skewed
            load = cfg.moe.num_experts / cfg.moe.top_k
            assert (float(aux.expert_load) == load) == case.endswith("skewed")
        else:
            trunk = cascade.init_trunk(jax.random.PRNGKey(5), cfg)
            feats = rng.standard_normal((6, 8)).astype(np.float32)
            head = {"proj": (0.3 * rng.standard_normal((8, cfg.d_model))).astype(np.float32),
                    "out": (0.05 * rng.standard_normal((cfg.d_model, 1))).astype(np.float32)}
            got = cascade._backbone_apply(cfg, trunk, head, jnp.asarray(feats))
            b = feats.shape[0]
            want = ref.tag(feats, np.broadcast_to(head["proj"], (b, 8, cfg.d_model)),
                           np.broadcast_to(head["out"][:, 0], (b, cfg.d_model)),
                           _ref_layers(cfg, trunk["layers"]), arch, 8)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("arch,want", [
    ("qwen3-1.7b", [(1, 28)]),
    ("gemma2-9b", [(2, 21)]),
    ("lfm2-24b-a2b", [(1, 2), (4, 9), (1, 1), (1, 1)]),
])
def test_layer_segments_cover_the_stack_in_order(arch, want):
    cfg = get_config(arch)
    segs = tf.layer_segments(cfg, cfg.num_layers)
    assert [(len(kinds), g) for kinds, g in segs] == want
    assert tuple(k for kinds, g in segs for _ in range(g) for k in kinds) == cfg.layer_kinds()
