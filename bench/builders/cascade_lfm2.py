"""Session whose expensive tagging level is an LFM2-MoE trunk served on the
chip: gated short convolutions, QK-normed GQA attention and dropless
sigmoid-routed experts.

The cascade, corpus and planner are ``bench/builders/cascade.py``'s (a
linear probe, an MLP probe and the model level over 64-d object features,
the probe levels applied at ingestion); only the trunk differs.  The trunk
is one pipeline stage of the published model: the configuration's first
``num_hidden_layers`` layers at published widths, every expert of each.
Trunk weights (matrices bfloat16, vectors and the expert bias float32),
heads, probes and features are made by the benchmark in one jitted call
from the seed and laid out as the program stacks them
(``repro.models.transformer.layer_segments``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import sys

import numpy as np

from bench import offline
from bench.reference import lfm2_moe

MIXERS = {"conv": "conv", "full_attention": "global"}


def _arch(cfg: dict):
    """The program's model description at the configuration's depth,
    checked against the published widths in the configuration file."""
    from repro.configs.archs import get_config

    mc = get_config(cfg["backbone_arch"], smoke=cfg["backbone_size"] == "smoke")
    want = dict(
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], rmsnorm_eps=cfg["norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        conv_kernel=cfg["conv_L_cache"], num_dense_layers=cfg["num_dense_layers"],
        layer_types=tuple(MIXERS[t] for t in cfg["layer_types"]), qk_norm=True,
        mlp_type="swiglu", num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        d_ff_expert=cfg["moe_intermediate_size"], router="sigmoid",
    )
    got = {k: getattr(mc.moe if hasattr(mc.moe, k) else mc, k) for k in want}
    if got != want:
        raise ValueError(f"program's {cfg['backbone_arch']} config {got} != published {want}")
    # the program's sigmoid router always carries the selection bias,
    # normalises the top-k weights and applies no routed scale
    fixed = dict(use_expert_bias=True, norm_topk_prob=True, routed_scaling_factor=1,
                 conv_bias=False)
    if any(cfg[k] != v for k, v in fixed.items()):
        raise ValueError(f"the program runs LFM2 only with {fixed}, the configuration "
                         f"has {({k: cfg[k] for k in fixed})}")
    return dataclasses.replace(mc, num_layers=cfg["num_hidden_layers"])


def active_params(cfg: dict) -> int:
    """Parameters a token passes through in the configuration's layers,
    from its published widths: per layer the mixer (conv: in_proj, taps,
    out_proj; attention: q/k/v/o) and the feed-forward (dense: three
    matrices; MoE: the router and top-k experts' three matrices).  Norm
    weights and the expert bias excluded."""
    d, k = cfg["hidden_size"], cfg["num_experts_per_tok"]
    hd = d // cfg["num_attention_heads"]
    conv = 3 * d * d + cfg["conv_L_cache"] * d + d * d
    attn = d * hd * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) + d * d
    dense = 3 * d * cfg["intermediate_size"]
    moe = d * cfg["num_experts"] + k * 3 * d * cfg["moe_intermediate_size"]
    total = 0
    for i, t in enumerate(cfg["layer_types"][: cfg["num_hidden_layers"]]):
        total += conv if t == "conv" else attn
        total += dense if i < cfg["num_dense_layers"] else moe
    return total


def weights_fn(cfg: dict, mc):
    """-> ``make(key)``: the trunk's stacked layers, features, heads, probe
    weights and the probe levels' outputs, made from ``key`` (jit it)."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as tf

    n, p, fd, w = cfg["objects"], cfg["predicates"], cfg["feature_dim"], cfg["probe_width"]
    d, H, KV, hd, ff = mc.d_model, mc.num_heads, mc.num_kv_heads, mc.head_dim, mc.d_ff
    E, fe, K = mc.moe.num_experts, mc.moe.d_ff_expert, mc.conv_kernel
    wdt = jnp.dtype(cfg["torch_dtype"])
    store = jnp.dtype(cfg["substrate_dtype"])
    positions = [(kind, g) for kinds, g in tf.layer_segments(mc, mc.num_layers) for kind in kinds]

    def make(key):
        ks = iter(jax.random.split(key, 64))

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)).astype(wdt)

        def vec(shape):
            return 1.0 + 0.1 * jax.random.normal(next(ks), shape)

        stacked = []
        for (mixer, ffn), g in positions:
            lp = {"ln1": vec((g, d)), "ln2": vec((g, d))}
            if mixer == "conv":
                lp["conv"] = {"in_proj": mat((g, d, 3 * d), d), "taps": mat((g, K, d), K),
                              "out_proj": mat((g, d, d), d)}
            else:
                lp["attn"] = {"wq": mat((g, d, H, hd), d), "wk": mat((g, d, KV, hd), d),
                              "wv": mat((g, d, KV, hd), d), "wo": mat((g, H, hd, d), H * hd),
                              "q_norm": vec((g, hd)), "k_norm": vec((g, hd))}
            if ffn == "mlp":
                lp["mlp"] = {"wg": mat((g, d, ff), d), "wu": mat((g, d, ff), d),
                             "wd": mat((g, ff, d), ff)}
            else:
                lp["moe"] = {"router": mat((g, d, E), d),
                             "expert_bias": cfg["expert_bias_scale"] * jax.random.normal(
                                 next(ks), (g, E)),
                             "wg": mat((g, E, d, fe), d), "wu": mat((g, E, d, fe), d),
                             "wd": mat((g, E, fe, d), fe)}
            stacked.append(lp)
        feats = jax.random.normal(next(ks), (n, fd))
        heads = {"proj": jax.random.normal(next(ks), (p, fd, d)) * cfg["head_scale"][0],
                 "out": jax.random.normal(next(ks), (p, d, 1)) * cfg["head_scale"][1]}
        lin = {"w": jax.random.normal(next(ks), (p, fd, 1)) / math.sqrt(fd),
               "b": jnp.zeros((p, 1))}
        mlp = {"w1": jax.random.normal(next(ks), (p, fd, w)) / math.sqrt(fd),
               "b1": jnp.zeros((p, w)),
               "w2": jax.random.normal(next(ks), (p, w, 1)) / math.sqrt(w),
               "b2": jnp.zeros((p, 1))}
        # the probe levels' outputs, written at ingestion [n, P, 2]
        pre0 = jax.nn.sigmoid(jnp.einsum("nf,pfo->npo", feats, lin["w"])[..., 0] + lin["b"][:, 0])
        hid = jax.nn.gelu(jnp.einsum("nf,pfw->npw", feats, mlp["w1"]) + mlp["b1"])
        pre1 = jax.nn.sigmoid(jnp.einsum("npw,pwo->npo", hid, mlp["w2"])[..., 0] + mlp["b2"][:, 0])
        probes = jnp.stack([pre0, pre1], axis=-1).astype(store)
        return tuple(stacked), feats, heads, lin, mlp, probes

    return make


def build(cfg: dict, traffic: dict, key_seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import EngineSession, Predicate
    from repro.core.state import SharedSubstrate
    from repro.enrich import cascade
    from repro.models import layers as nn
    from repro.models import moe
    from repro.models import transformer as tf

    mc = _arch(cfg)
    n, p, fd, w = cfg["objects"], cfg["predicates"], cfg["feature_dim"], cfg["probe_width"]
    L, d = mc.num_layers, mc.d_model
    store = jnp.dtype(cfg["substrate_dtype"])
    segs = tf.layer_segments(mc, L)
    positions = [(kind, g) for kinds, g in segs for kind in kinds]
    # layer i of the stage -> (stacked position, group)
    where, base = [], 0
    for kinds, g in segs:
        where += [(base + j, r) for r in range(g) for j in range(len(kinds))]
        base += len(kinds)

    layers, feats, heads, lin, mlp, probes = jax.jit(weights_fn(cfg, mc))(
        jax.random.PRNGKey(key_seed))
    trunk = {"layers": layers}
    active = active_params(cfg)
    flops = [2.0 * fd, 2.0 * fd * w * 2, 2.0 * active * cfg["backbone_tokens"]]
    if cfg["backbone_tokens"] != cascade.N_BACKBONE_TOKENS:
        raise ValueError(f"the program's model level sees {cascade.N_BACKBONE_TOKENS} "
                         f"positions, the configuration {cfg['backbone_tokens']}")
    casc = [
        [cascade.CascadeLevel("linear", jax.tree.map(lambda x: x[i], lin),
                              cascade._linear_probe_apply, flops[0]),
         cascade.CascadeLevel("mlp", jax.tree.map(lambda x: x[i], mlp),
                              cascade._mlp_probe_apply, flops[1]),
         cascade.CascadeLevel(f"backbone:{mc.name}",
                              (trunk, jax.tree.map(lambda x: x[i], heads)),
                              None, flops[2], cfg=mc)]
        for i in range(p)
    ]
    bank = cascade.ModelCascadeBank(cascades=casc, features=feats)
    aucs = np.broadcast_to(np.asarray(cfg["level_aucs"], np.float32), (p, 3))
    tab = offline.analytic_tables(aucs, cfg["table_bins"])
    combine, table, costs_j, engine = offline.session_inputs(
        {k: jnp.asarray(v) for k, v in tab.items()}, bank.costs, cfg)
    preds = [Predicate(i, 1) for i in range(p)]
    session = EngineSession(
        [q.positive() for q in preds], table, combine, costs_j,
        capacity=n, max_tenants=cfg["max_tenants"], config=engine, bank=bank,
    )
    state = session.init_state(jnp.full((n, p, 3), cfg["prior"], jnp.float32))
    sub = state.substrate
    state = session.refresh(dataclasses.replace(state, substrate=SharedSubstrate(
        func_probs=sub.func_probs.at[:, :, :2].set(probes),
        exec_mask=sub.exec_mask.at[:, :, :2].set(True),
        cost_spent=sub.cost_spent,
    )))

    arch = dict(norm_eps=cfg["norm_eps"], rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
                num_experts_per_tok=cfg["num_experts_per_tok"],
                routed_scaling_factor=float(cfg["routed_scaling_factor"]))
    host_feats = np.asarray(jax.device_get(feats))
    host_heads = {k: np.asarray(v, np.float32) for k, v in jax.device_get(heads).items()}

    def trunk_layers(weights=None):
        q = {None: lambda x, n_in: np.asarray(x, np.float32),
             "int8": lfm2_moe.quantize, "fp8": lfm2_moe.quantize_fp8}[weights]

        def experts(x, n_in):  # each expert's matrix on its own channels
            return np.stack([q(x[e], n_in) for e in range(x.shape[0])])

        f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
        for i in range(L):
            pos, g = where[i]
            (mixer, ffn), _ = positions[pos]
            lp = jax.device_get(jax.tree.map(lambda x: x[g], layers[pos]))
            out = dict(mixer="conv" if mixer == "conv" else "attention", ffn=ffn,
                       ln1=f32(lp["ln1"]), ln2=f32(lp["ln2"]))
            if mixer == "conv":
                c = lp["conv"]
                out.update(in_proj=q(c["in_proj"], 1), taps=q(c["taps"], 1),
                           out_proj=q(c["out_proj"], 1))
            else:
                a = lp["attn"]
                out.update(wq=q(a["wq"], 1), wk=q(a["wk"], 1), wv=q(a["wv"], 1),
                           wo=q(a["wo"], 2), q_norm=f32(a["q_norm"]), k_norm=f32(a["k_norm"]))
            if ffn == "mlp":
                m = lp["mlp"]
                out.update(wg=q(m["wg"], 1), wu=q(m["wu"], 1), wd=q(m["wd"], 1))
            else:
                m = lp["moe"]
                out.update(router=q(m["router"], 1), expert_bias=f32(m["expert_bias"]),
                           wg=experts(m["wg"], 1), wu=experts(m["wu"], 1),
                           wd=experts(m["wd"], 1))
            yield out

    @functools.lru_cache(maxsize=None)
    def layer_fn(kind):
        """The program's layer ``kind`` on one object batch -> (output,
        chosen experts, margin) with the choice read from ``moe.route`` and
        the margin the k-th choice score holds over the (k+1)-th (both None
        for a dense layer)."""
        mixer, ffn = kind

        @jax.jit
        def f(lp, x):
            pos = jnp.broadcast_to(jnp.arange(x.shape[1])[None], x.shape[:2])
            mixed = {k: v for k, v in lp.items() if k not in ("mlp", "moe")}
            mid = tf.block_apply(mixed, mc, mixer, x, pos, causal=False)[0]
            chosen = margin = None
            if ffn == "moe":
                h2 = nn.rmsnorm(mid, lp["ln2"], mc.rmsnorm_eps)
                chosen, _, logits = moe.route(lp["moe"], mc, h2.reshape(-1, d))
                top = jax.lax.top_k(jax.nn.sigmoid(logits) + lp["moe"]["expert_bias"],
                                    mc.moe.top_k + 1)[0]
                margin = top[:, -2] - top[:, -1]
            return tf.block_apply(lp, mc, mixer, x, pos, causal=False)[0], chosen, margin

        return f

    def routing_gap(o, q, ref_routes) -> dict:
        """Where the program chose other experts than the reference, on
        these objects: the share of (token, expert layer) routings over
        all layers and per layer in depth order, and the median margin of
        the program's k-th choice over its (k+1)-th, over all routings and
        over the differing ones (near-ties flip under rounding)."""
        x = jnp.einsum("bf,bfd->bd", feats[jnp.asarray(o)], heads["proj"][jnp.asarray(q)])
        x = jnp.tile(x[:, None, :], (1, cfg["backbone_tokens"], 1)).astype(mc.activation_dtype)
        differs, margins, layer = [], [], 0
        for i in range(L):
            pos, g = where[i]
            kind = positions[pos][0]
            x, chosen, margin = layer_fn(kind)(jax.tree.map(lambda a: a[g], layers[pos]), x)
            if chosen is not None:
                got = np.sort(np.asarray(chosen), axis=-1)
                want = np.sort(ref_routes[layer], axis=-1)
                differs.append(np.any(got != want, axis=-1))
                margins.append(np.asarray(margin))
                layer += 1
        differ, margin = np.concatenate(differs), np.concatenate(margins)
        return dict(share=float(differ.mean()),
                    by_layer=[round(float(v.mean()), 4) for v in differs],
                    margin_all=float(np.median(margin)),
                    margin_differ=float(np.median(margin[differ])) if differ.any() else None)

    memo: dict = {}

    def model_value(o, q, fn, weights=None):
        """The reference's outputs for model-level triples (o, q, fn == 2);
        ``weights`` ("int8" or "fp8") is a control's step below bfloat16.
        At the configuration's weights it also logs how often the program
        routed a token to other experts than the reference did.  The last
        outputs at each weights are kept: the check asks for them again."""
        if np.any(fn != 2):
            raise ValueError("only model-level triples are checked against the trunk")
        key = (np.asarray(o).tobytes(), np.asarray(q).tobytes())
        if memo.get(weights, (None,))[0] == key:
            return memo[weights][1]
        routes = [] if weights is None else None
        out = lfm2_moe.tag(host_feats[o], host_heads["proj"][q], host_heads["out"][q, :, 0],
                           trunk_layers(weights), arch, cfg["backbone_tokens"], routes)
        if routes is not None:
            gap = routing_gap(o, q, routes)
            print(f"[bench] routing_gap {gap['share']} over {len(o)} model triples x "
                  f"{cfg['backbone_tokens']} positions x {len(routes)} expert layers; by "
                  f"layer {gap['by_layer']}; median choice margin {gap['margin_all']} over "
                  f"all routings, {gap['margin_differ']} over differing ones",
                  file=sys.stderr, flush=True)
        memo[weights] = (key, out)
        return out

    def model_triples(st) -> int:
        return int(jnp.sum(st.substrate.exec_mask[:, :, 2]))

    return dict(
        session=session,
        state=state,
        predicates=preds,
        initial_rows=n,
        state_capacity=n,
        store_bytes=store.itemsize,
        stream_rows=None,
        corpus=None,
        model_value=model_value,
        model_triples=model_triples,
        active_params=active,
        moe_shape=dict(layers=sum(mc.ffn_of_layer(i) == "moe" for i in range(L)),
                       top_k=mc.moe.top_k, experts=mc.moe.num_experts, d_model=d,
                       d_ff_expert=mc.moe.d_ff_expert,
                       weight_bytes=jnp.dtype(cfg["torch_dtype"]).itemsize),
        reference=dict(tables=tab, costs=np.asarray(bank.costs, np.float32)),
    )
