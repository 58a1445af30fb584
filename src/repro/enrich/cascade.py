"""Model-cascade tagging bank: PIQUE's tagging functions as real models.

Each tag type gets a cascade of classifiers over object feature vectors,
cheap -> expensive (the paper's DT -> GNB -> RF -> SVM spectrum, DESIGN.md
section 3):

    level 0: linear probe                 (the pre-executed cheapest function)
    level 1: 2-layer MLP probe
    level 2: assigned-arch-backbone head (the reduced smoke config on CPU;
             the published widths on the chip, ``serve --backbone-size
             published``)

Costs are analytic FLOPs divided by ``PEAK_FLOPS``: a fixed planner cost
unit that ranks levels against each other, not a measured or predicted
device time (it is never reported as one).  Qualities are measured AUC on
a held-out validation split.

``ModelCascadeBank`` is a *traceable* bank (``supports_scan == True``): at
construction the per-(predicate, level) parameters are stacked into
homogeneous ``[P]``-leading pytrees (linear and MLP probes stack directly;
the backbone level is ONE shared trunk with stacked per-predicate heads),
and ``execute`` is a pure fixed-shape JAX function — the merged plan's lanes
are sorted by (pred, level) key inside the trace, each level runs as one
masked batched forward over the full lane vector (features gathered once,
``vmap`` over predicate heads), and probabilities scatter back through the
inverse permutation.  That lets the whole plan -> execute -> apply epoch
fuse into ``EpochProgram.run_scan`` with zero host round-trips per epoch.
Every array ``execute`` reads (features, probe stacks, the backbone trunk
and heads) is the ``params`` pytree, which the superstep takes as a jit
ARGUMENT: a closed-over trunk would be baked into each executable as
compile-time constants (gigabytes at published widths).
``execute_host`` keeps the legacy host-side numpy grouping (one jitted call
per (pred, level)) as the parity reference and benchmark baseline.

Ragged cascades (predicates with fewer levels) pad ``costs`` with a LARGE
sentinel (never zero: the planner divides benefit by cost, and a free
nonexistent level would win every epoch) and publish an ``available``
[P, F] mask; engines exclude unavailable (pred, level) pairs structurally
via the quarantine channel.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.core.plan import Plan
from repro.models import transformer as tf
from repro.models.config import ModelConfig

# The planner's cost unit: analytic FLOPs per object / PEAK_FLOPS.  A fixed
# scale (the TPU v5e bf16 peak), so level costs stay comparable across
# configs; it is NOT a device-time estimate and is never printed as one.
PEAK_FLOPS = 197e12

# Cost padding for (pred, level) slots a ragged cascade bank does not have.
# Eq. 11 ranks triples by benefit / cost, so a missing level must look
# prohibitively expensive, never free: ~30 device-years at peak keeps the
# ratio at effectively zero while staying far from f32 overflow when costs
# are summed over a plan.
SENTINEL_COST_S = 1e9

# The backbone head tiles each projected feature vector into this many
# token positions before the trunk (a "patch sequence" stand-in).
N_BACKBONE_TOKENS = 8


def _linear_probe_init(key, d, width=0):
    return {"w": jax.random.normal(key, (d, 1)) * (1 / math.sqrt(d)),
            "b": jnp.zeros((1,))}


def _linear_probe_apply(params, x):
    return jax.nn.sigmoid(x @ params["w"] + params["b"])[:, 0]


def _mlp_probe_init(key, d, width=256):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d, width)) * (1 / math.sqrt(d)),
        "b1": jnp.zeros((width,)),
        "w2": jax.random.normal(k2, (width, 1)) * (1 / math.sqrt(width)),
        "b2": jnp.zeros((1,)),
    }


def _mlp_probe_apply(params, x):
    h = jax.nn.gelu(x @ params["w1"] + params["b1"])
    return jax.nn.sigmoid(h @ params["w2"] + params["b2"])[:, 0]


@dataclasses.dataclass
class CascadeLevel:
    name: str
    params: object
    apply_fn: Callable  # (params, features [B, D]) -> probs [B]
    flops_per_object: float
    cfg: Optional[ModelConfig] = None  # backbone levels carry their config

    @property
    def cost_seconds(self) -> float:
        return self.flops_per_object / PEAK_FLOPS


def _backbone_apply(cfg: ModelConfig, trunk_params, head_params, feats):
    """Features -> token-ish patches -> reduced backbone -> mean-pool ->
    sigmoid head.  Shared by the per-level closure and the fused bank."""
    b = feats.shape[0]
    x = feats @ head_params["proj"]  # [B, d_model]
    x = jnp.tile(x[:, None, :], (1, N_BACKBONE_TOKENS, 1)).astype(
        cfg.activation_dtype
    )
    pos = jnp.broadcast_to(
        jnp.arange(N_BACKBONE_TOKENS)[None], (b, N_BACKBONE_TOKENS)
    )
    h, _, _ = tf.stack_apply(
        trunk_params["layers"], cfg, x, pos, cfg.num_layers, causal=False
    )
    pooled = jnp.mean(h.astype(jnp.float32), axis=1)
    return jax.nn.sigmoid(pooled @ head_params["out"])[:, 0]


def init_trunk(key, cfg: ModelConfig) -> dict:
    """The backbone's transformer layers, weight matrices at the activation
    dtype.

    The tagging head feeds projected features straight into the layers, so
    the token embedding is never built.  The layers draw the same random
    stream as ``Model(cfg).init_params(key)["layers"]``.  Only the stacked
    weight tensors (ndim >= 3) are cast, and every forward casts exactly
    those to the activation dtype at use, so the cast halves the trunk's
    bytes without changing what it computes; vectors (norms, SSM decay and
    bias terms) stay f32.
    """

    @jax.jit
    def init(k):
        layers, _ = tf.stack_init(
            jax.random.split(k, 8)[1], cfg, cfg.num_layers,
            cross=cfg.encoder is not None,
        )
        return jax.tree.map(
            lambda x: x.astype(cfg.activation_dtype) if x.ndim >= 3 else x,
            layers,
        )

    return {"layers": init(key)}


@functools.lru_cache(maxsize=None)
def _backbone_fns(cfg: ModelConfig):
    """(apply, head_grad) jitted once per config, trunk as an argument.

    ``apply((trunk, head), feats) -> probs``; ``head_grad(trunk, head,
    feats, labels)`` is the head's NLL gradient with the trunk frozen.
    """

    def apply(params, feats):
        trunk, head = params
        return _backbone_apply(cfg, trunk, head, feats)

    def loss(trunk, head, feats, y):
        pr = jnp.clip(apply((trunk, head), feats), 1e-6, 1 - 1e-6)
        return -jnp.mean(y * jnp.log(pr) + (1 - y) * jnp.log(1 - pr))

    return jax.jit(apply), jax.jit(jax.grad(loss, argnums=1))


def _backbone_level(
    key,
    cfg: ModelConfig,
    feature_dim: int,
    trunk_params=None,
) -> CascadeLevel:
    """Transformer-backbone tagging head.  ``trunk_params`` shares ONE trunk
    across predicates (per-predicate heads only) — the layout the fused bank
    requires; when omitted a private trunk is initialized."""
    if trunk_params is None:
        trunk_params = init_trunk(key, cfg)
    k2 = jax.random.fold_in(key, 1)
    head = {
        "proj": jax.random.normal(k2, (feature_dim, cfg.d_model)) * 0.05,
        "out": jax.random.normal(jax.random.fold_in(k2, 1), (cfg.d_model, 1)) * 0.05,
    }
    apply_fn, _ = _backbone_fns(cfg)

    # FLOP-honest cost: 2 * active params per token, N_BACKBONE_TOKENS tokens
    flops = 2.0 * cfg.param_counts()["active"] * N_BACKBONE_TOKENS
    return CascadeLevel(
        name=f"backbone:{cfg.name}",
        params=(trunk_params, head),
        apply_fn=apply_fn,
        flops_per_object=flops,
        cfg=cfg,
    )


def build_cascade(
    key,
    feature_dim: int,
    backbone_cfg: Optional[ModelConfig] = None,
    backbone_trunk=None,
) -> list[CascadeLevel]:
    ks = jax.random.split(key, 4)
    levels = [
        CascadeLevel("linear", _linear_probe_init(ks[0], feature_dim),
                     _linear_probe_apply, 2.0 * feature_dim),
        CascadeLevel("mlp", _mlp_probe_init(ks[1], feature_dim),
                     _mlp_probe_apply, 2.0 * feature_dim * 256 * 2),
    ]
    if backbone_cfg is not None:
        levels.append(
            _backbone_level(ks[2], backbone_cfg, feature_dim,
                            trunk_params=backbone_trunk)
        )
    return levels


def build_cascade_suite(
    key,
    num_preds: int,
    feature_dim: int,
    backbone_cfg: Optional[ModelConfig] = None,
) -> list[list[CascadeLevel]]:
    """One cascade per predicate with the stacked-bank layout: private
    linear/MLP probes, one SHARED backbone trunk with per-predicate heads."""
    trunk = None
    if backbone_cfg is not None:
        trunk = init_trunk(jax.random.fold_in(key, 999), backbone_cfg)
    return [
        build_cascade(
            jax.random.fold_in(key, i), feature_dim,
            backbone_cfg=backbone_cfg, backbone_trunk=trunk,
        )
        for i in range(num_preds)
    ]


def train_level(
    level: CascadeLevel, feats: jax.Array, labels: jax.Array,
    steps: int = 200, lr: float = 0.05,
) -> CascadeLevel:
    """Fit a level to planted labels with NLL descent.  Backbone levels
    train only the (proj, out) head with the backbone frozen; the trunk is
    an argument of the jitted gradient, never a compile-time constant."""
    y = labels.astype(jnp.float32)

    if level.name.startswith("backbone"):
        backbone, head = level.params
        _, head_grad = _backbone_fns(level.cfg)
        for _ in range(max(steps // 2, 50)):
            g = head_grad(backbone, head, feats, y)
            head = jax.tree.map(lambda t, gg: t - lr * gg, head, g)
        return dataclasses.replace(level, params=(backbone, head))

    def loss(p):
        pr = jnp.clip(level.apply_fn(p, feats), 1e-6, 1 - 1e-6)
        return -jnp.mean(y * jnp.log(pr) + (1 - y) * jnp.log(1 - pr))

    g = jax.jit(jax.grad(loss))
    params = level.params
    for _ in range(steps):
        params = jax.tree.map(lambda t, gg: t - lr * gg, params, g(params))
    return dataclasses.replace(level, params=params)


@dataclasses.dataclass
class ModelCascadeBank:
    """Tagging bank backed by model cascades (one per predicate).

    Traceable: ``execute`` is a pure JAX function over stacked parameters,
    so the bank runs INSIDE the fused scan superstep.  ``execute_host`` is
    the legacy per-(pred, level) host dispatch kept as the parity oracle.
    """

    cascades: Sequence[Sequence[CascadeLevel]]  # [P][<=F]
    features: jax.Array  # [N, D]
    costs: jax.Array = None  # [P, F] planner cost units (filled in __post_init__)
    available: jax.Array = None  # [P, F] bool (filled in __post_init__)

    # the scan superstep may trace this bank's execute (see core.executor)
    supports_scan = True

    def __post_init__(self):
        p = len(self.cascades)
        f = max(len(c) for c in self.cascades)
        # missing levels of a ragged bank: sentinel cost, unavailable —
        # NEVER zero cost (a free level would have infinite benefit/cost)
        costs = np.full((p, f), SENTINEL_COST_S, np.float32)
        avail = np.zeros((p, f), bool)
        for i, c in enumerate(self.cascades):
            for j, lvl in enumerate(c):
                costs[i, j] = lvl.cost_seconds
                avail[i, j] = True
        self.costs = jnp.asarray(costs)
        self.available = jnp.asarray(avail)
        self.features = jnp.asarray(self.features)
        self._jitted = {}
        self._levels, level_params = self._build_stack(p, f)
        self.params = {"features": self.features, "levels": level_params}

    @property
    def num_levels(self) -> int:
        return self.costs.shape[1]

    # ---- stacked-parameter construction ------------------------------------

    def _build_stack(self, p: int, f: int) -> tuple[list, list]:
        """Per level: one homogeneous [P]-leading parameter stack.

        Linear/MLP probes stack leaf-wise (predicates missing the level get
        zero-filled placeholders, masked out by ``available``).  Backbone
        levels must share ONE trunk across predicates; only the (proj, out)
        heads stack.  -> (static per-level descriptions, per-level array
        pytrees); the arrays go into ``params``.
        """
        levels, stack = [], []
        for j in range(f):
            present = {i: c[j] for i, c in enumerate(self.cascades) if len(c) > j}
            template = next(iter(present.values()))
            if template.name.startswith("backbone"):
                trunks = {id(lvl.params[0]) for lvl in present.values()}
                if len(trunks) != 1:
                    raise ValueError(
                        "backbone cascade level requires one shared trunk "
                        "with per-predicate heads (build_cascade_suite); got "
                        f"{len(trunks)} distinct trunks at level {j}"
                    )
                zero_head = jax.tree.map(jnp.zeros_like, template.params[1])
                heads = [
                    present[i].params[1] if i in present else zero_head
                    for i in range(p)
                ]
                levels.append(dict(kind="backbone", cfg=template.cfg))
                stack.append(dict(
                    trunk=template.params[0],
                    heads=jax.tree.map(lambda *xs: jnp.stack(xs), *heads),
                ))
            else:
                fns = {lvl.apply_fn for lvl in present.values()}
                if len(fns) != 1:
                    raise ValueError(
                        f"cascade level {j} mixes apply functions; stacked "
                        "dispatch needs one architecture per level"
                    )
                zero = jax.tree.map(jnp.zeros_like, template.params)
                params = [
                    present[i].params if i in present else zero
                    for i in range(p)
                ]
                levels.append(dict(kind="probe", apply=template.apply_fn))
                stack.append(jax.tree.map(lambda *xs: jnp.stack(xs), *params))
        return levels, stack

    def _apply(self, pred: int, fn: int):
        key = (pred, fn)
        if key not in self._jitted:
            lvl = self.cascades[pred][fn]
            self._jitted[key] = jax.jit(lvl.apply_fn)
        return self._jitted[key]

    def subset(self, cols) -> "ModelCascadeBank":
        """Bank restricted to a subset of predicate columns (shares cascade
        params and features; used for independent-operator baselines against
        the multi-query engine)."""
        return ModelCascadeBank(
            cascades=[self.cascades[int(c)] for c in cols],
            features=self.features,
        )

    # ---- execution ----------------------------------------------------------

    def execute(self, plan: Plan, params=None) -> jax.Array:
        """Probabilities [M] alone, as every bank's ``execute`` returns them
        to the facades' per-epoch paths; the superstep calls
        ``execute_with_stats``."""
        return self.execute_with_stats(plan, params)[0]

    def execute_with_stats(self, plan: Plan, params=None):
        """Fused traceable execute: every unique (object, pred, level) triple
        of the merged plan in one fixed-shape program.

        Lanes are sorted by (pred, level) key (invalid lanes to the back),
        features are gathered once, and each cascade level runs as ONE
        masked batched forward — probes ``vmap`` over the stacked predicate
        heads, the backbone runs a single shared-trunk pass with per-lane
        head gathers (skipped in-trace via ``lax.cond`` on epochs where the
        planner selected no backbone lane).  Results scatter back through
        the inverse permutation;
        unmatched/invalid lanes return the 0.5 prior, matching
        ``execute_host`` lane for lane.

        Works unchanged for single-query plans and for the multi-query
        engine's merged deduplicated plans, and — because every operand is a
        fixed-shape jnp array — inside ``jit`` / ``lax.scan``.  ``params``
        (default: this bank's own ``params``) supplies every array read, so
        a traced caller passes them in as arguments.

        -> (probabilities [M], stats): ``stats["expert_load"]`` is the most
        tokens routed to one expert over the mean, the largest over the
        trunk's expert layers (0 where the trunk has none or did not run).
        """
        if params is None:
            params = self.params
        p_num = len(self.cascades)
        f_num = self.num_levels
        m = plan.object_idx.shape[0]
        n = params["features"].shape[0]
        valid = plan.valid
        obj = jnp.where(valid, jnp.clip(plan.object_idx, 0, n - 1), 0)
        prd = jnp.where(valid, jnp.clip(plan.pred_idx, 0, p_num - 1), 0)
        fns = jnp.where(valid, jnp.clip(plan.func_idx, 0, f_num - 1), 0)

        # stable lane sort by (pred, level); invalid lanes sort past P*F
        key = jnp.where(valid, prd * f_num + fns, p_num * f_num)
        order = jnp.argsort(key)
        inv = jnp.argsort(order)
        s_obj, s_prd, s_fn = obj[order], prd[order], fns[order]
        s_valid = valid[order]
        feats = params["features"][s_obj].astype(jnp.float32)  # [M, D]
        lane = jnp.arange(m)

        out = jnp.full((m,), 0.5, jnp.float32)
        expert_load = jnp.zeros((), jnp.float32)
        for j, (level, arrays) in enumerate(zip(self._levels, params["levels"])):
            on = s_valid & (s_fn == j) & self.available[s_prd, j]
            if level["kind"] == "backbone":
                cfg = level["cfg"]
                heads = arrays["heads"]

                @tracing.scope(tracing.TRUNK)
                def _backbone_probs(operands, cfg=cfg, heads=heads, trunk=arrays["trunk"]):
                    feats, s_prd = operands
                    # per-predicate input/output heads via vmap-shaped
                    # einsums, one shared trunk pass over all M lanes
                    x_all = jnp.einsum("md,pdk->pmk", feats, heads["proj"])
                    x = x_all[s_prd, lane]  # [M, d_model]
                    x = jnp.tile(
                        x[:, None, :], (1, N_BACKBONE_TOKENS, 1)
                    ).astype(cfg.activation_dtype)
                    pos = jnp.broadcast_to(
                        jnp.arange(N_BACKBONE_TOKENS)[None],
                        (m, N_BACKBONE_TOKENS),
                    )
                    h, _, aux = tf.stack_apply(
                        trunk["layers"], cfg, x, pos, cfg.num_layers,
                        causal=False,
                    )
                    pooled = jnp.mean(h.astype(jnp.float32), axis=1)
                    logits = jnp.einsum("mk,pko->pmo", pooled, heads["out"])
                    return jax.nn.sigmoid(logits[s_prd, lane, 0]), aux.expert_load

                # Skip the trunk entirely on epochs where the planner put no
                # lane at this level — the in-trace twin of execute_host's
                # ``if not sel.any(): continue``.  The branch result is only
                # read where ``on`` holds, so the skip value never escapes.
                probs, load = jax.lax.cond(
                    jnp.any(on),
                    _backbone_probs,
                    lambda operands: (jnp.full((m,), 0.5, jnp.float32),
                                      jnp.zeros((), jnp.float32)),
                    (feats, s_prd),
                )
                expert_load = jnp.maximum(expert_load, load)
            else:
                per_pred = jax.vmap(level["apply"], in_axes=(0, None))(
                    arrays, feats
                )  # [P, M]
                probs = per_pred[s_prd, lane]
            out = jnp.where(on, probs.astype(jnp.float32), out)
        return out[inv], {"expert_load": expert_load}

    def execute_host(self, plan: Plan) -> jax.Array:
        """Legacy host dispatch: group triples by (pred, level) on the host
        and run one jitted forward per non-empty group.

        The pre-fusion execution path, kept as the parity oracle for
        ``execute`` and the per-epoch-loop benchmark baseline.
        """
        obj = np.asarray(plan.object_idx)
        prd = np.asarray(plan.pred_idx)
        fns = np.asarray(plan.func_idx)
        valid = np.asarray(plan.valid)
        out = np.full(obj.shape, 0.5, np.float32)
        for p in range(len(self.cascades)):
            for f in range(len(self.cascades[p])):
                sel = valid & (prd == p) & (fns == f)
                if not sel.any():
                    continue
                idx = obj[sel]
                feats = self.features[jnp.asarray(idx)]
                probs = self._apply(p, f)(self.cascades[p][f].params, feats)
                out[sel] = np.asarray(probs, np.float32)
        return jnp.asarray(out)
