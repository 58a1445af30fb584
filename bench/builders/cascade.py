"""Session whose expensive tagging level is a transformer served on the chip.

Each predicate has a cascade of three levels over 64-d object features: a
linear probe, an MLP probe and a Qwen3 trunk with a per-predicate head, the
trunk at its published widths and depth (one trunk shared by every
predicate's head, the layout the program's traceable bank runs).  The two
probe levels ran at ingestion: set-up writes their outputs and executed bits
into the substrate and refreshes, so every triple the planner buys in the
window is a trunk triple.

Features, probe and trunk weights are made by the benchmark in one jitted
call from the seed, at the dtypes they are served in (trunk matrices
bfloat16, vectors float32); the decision table and combine weights come from
the declared level qualities (``bench/offline.analytic_tables``).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from bench import counts, offline
from bench.reference import qwen3


def _arch(cfg: dict):
    """The program's model description, checked against the published
    widths in the configuration file."""
    from repro.configs.archs import get_config

    mc = get_config(cfg["backbone_arch"], smoke=cfg["backbone_size"] == "smoke")
    b = cfg["backbone"]
    want = dict(num_layers=b["num_hidden_layers"], d_model=b["hidden_size"],
                num_heads=b["num_attention_heads"], num_kv_heads=b["num_key_value_heads"],
                head_dim=b["head_dim"], d_ff=b["intermediate_size"],
                rmsnorm_eps=b["rms_norm_eps"], rope_theta=float(b["rope_theta"]),
                qk_norm=True, mlp_type="swiglu")
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        raise ValueError(f"program's {cfg['backbone_arch']} config {got} != published {want}")
    return mc


def build(cfg: dict, traffic: dict, key_seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import EngineSession, Predicate
    from repro.core.state import SharedSubstrate
    from repro.enrich import cascade

    mc = _arch(cfg)
    b = cfg["backbone"]
    n, p, fd, w = cfg["objects"], cfg["predicates"], cfg["feature_dim"], cfg["probe_width"]
    L, d, H, KV, hd, ff = (b["num_hidden_layers"], b["hidden_size"], b["num_attention_heads"],
                           b["num_key_value_heads"], b["head_dim"], b["intermediate_size"])
    wdt = jnp.dtype(b["torch_dtype"])
    store = jnp.dtype(cfg["substrate_dtype"])

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 32))

        def mat(shape, fan_in):
            return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)).astype(wdt)

        def vec(shape):
            return 1.0 + 0.1 * jax.random.normal(next(ks), shape)

        layers = {
            "ln1": vec((L, d)), "ln2": vec((L, d)),
            "attn": {"wq": mat((L, d, H, hd), d), "wk": mat((L, d, KV, hd), d),
                     "wv": mat((L, d, KV, hd), d), "wo": mat((L, H, hd, d), H * hd),
                     "q_norm": vec((L, hd)), "k_norm": vec((L, hd))},
            "mlp": {"wg": mat((L, d, ff), d), "wu": mat((L, d, ff), d), "wd": mat((L, ff, d), ff)},
        }
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
        return layers, feats, heads, lin, mlp, probes

    layers, feats, heads, lin, mlp, probes = make(jax.random.PRNGKey(key_seed))
    trunk = {"layers": (layers,)}
    active = counts.transformer_active_params(L, d, H, KV, hd, ff)
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

    arch = dict(rms_norm_eps=b["rms_norm_eps"], rope_theta=float(b["rope_theta"]))
    host_feats = np.asarray(jax.device_get(feats))
    host_heads = {k: np.asarray(v, np.float32) for k, v in jax.device_get(heads).items()}

    def trunk_layers(weights=None):
        f32 = {None: lambda x, n_in: np.asarray(x, np.float32),
               "int8": qwen3.quantize, "fp8": qwen3.quantize_fp8}[weights]
        for i in range(L):
            lp = jax.device_get(jax.tree.map(lambda x: x[i], layers))
            a, m = lp["attn"], lp["mlp"]
            yield dict(
                ln1=np.asarray(lp["ln1"], np.float32), ln2=np.asarray(lp["ln2"], np.float32),
                q_norm=np.asarray(a["q_norm"], np.float32), k_norm=np.asarray(a["k_norm"], np.float32),
                wq=f32(a["wq"], 1), wk=f32(a["wk"], 1), wv=f32(a["wv"], 1), wo=f32(a["wo"], 2),
                wg=f32(m["wg"], 1), wu=f32(m["wu"], 1), wd=f32(m["wd"], 1),
            )

    def model_value(o, q, fn, weights=None):
        """The reference's outputs for model-level triples (o, q, fn == 2);
        ``weights`` ("int8" or "fp8") is a control's step below bfloat16."""
        if np.any(fn != 2):
            raise ValueError("only model-level triples are checked against the trunk")
        return qwen3.tag(host_feats[o], host_heads["proj"][q], host_heads["out"][q, :, 0],
                         trunk_layers(weights), arch, cfg["backbone_tokens"])

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
        reference=dict(tables=tab, costs=np.asarray(bank.costs, np.float32)),
    )
