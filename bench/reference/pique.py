"""Plain reference of one PIQUE session epoch, in numpy.

It follows the paper (arXiv:1805.12033) and the session's documented
semantics, and imports nothing of the program:

* derive (Eq. 1, 2, 5): masked logistic pooling of the executed functions'
  outputs, binary entropy, the conjunctive joint per tenant slot;
* answer selection (Theorem 1, exact): the prefix of the descending joint
  order that maximises expected F-alpha, ties by lowest row;
* Eq. 11 scoring with best-function selection: decision-table deltas per
  remaining function, the optimistic inverse-entropy root by a 4096-bin
  table, benefit = P * P_hat / cost, strict first maximum over functions;
* candidates: outside the answer, or entropy at or above the masked median
  (floor 0.35); plan = per-slot top ``plan_size`` by benefit, ties by lowest
  flat (row, predicate) index; dedup merge across slots;
* execution: a gather from the pre-materialised outputs (simulated bank) or a
  caller-given function (a model bank); write-once charging; fair-share
  ledger attribution over the slots that wanted each charged triple.

``Precision`` rounds after every elementary operation: float32 is the
configuration's arithmetic, bfloat16 is the control one step below it.
Storage follows the configuration's substrate dtype (bfloat16 rounding of
the stored derived values).
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
LN2 = 0.6931471805599453


class Precision:
    def __init__(self, name: str):
        self.name = name
        self.dtype = np.float32 if name == "float32" else BF16

    def __call__(self, x):
        x = np.asarray(x, np.float32)
        if self.dtype is np.float32:
            return x
        return x.astype(BF16).astype(np.float32)


F32 = Precision("float32")


def store(x):
    """Round to the substrate's storage dtype (bfloat16), kept as float32."""
    return np.asarray(x, np.float32).astype(BF16).astype(np.float32)


def inverse_entropy_lut(bins: int = 4096) -> np.ndarray:
    """Upper root p >= 0.5 of H(p) = h on a uniform h grid."""
    p = 1.0 - np.logspace(-12, np.log10(0.5), 65536)[::-1]
    with np.errstate(divide="ignore", invalid="ignore"):
        h = -(
            np.where(p > 0, p * np.log2(np.maximum(p, 1e-300)), 0.0)
            + np.where(p < 1, (1 - p) * np.log2(np.maximum(1 - p, 1e-300)), 0.0)
        )
    return np.interp(np.linspace(0.0, 1.0, bins), h[::-1], p[::-1]).astype(np.float32)


LUT = inverse_entropy_lut()


@dataclasses.dataclass
class Model:
    """Configuration inputs the epoch reads: combine weights, decision
    table, costs and the engine's constants."""

    weights: np.ndarray  # [P, F]
    bias: np.ndarray  # [P]
    rho: np.ndarray  # [P]
    delta_h_all: np.ndarray  # [P, 2^F, B, F]
    costs: np.ndarray  # [P, F]
    plan_size: int
    prior: float = 0.5
    alpha: float = 1.0
    entropy_floor: float = 0.35


@dataclasses.dataclass
class State:
    """One session state, host side (stored values as float32)."""

    func_probs: np.ndarray  # [C, P, F]
    exec_mask: np.ndarray  # [C, P, F] bool
    pred_prob: np.ndarray  # [C, P]
    uncertainty: np.ndarray  # [C, P]
    joint: np.ndarray  # [S, C]
    in_answer: np.ndarray  # [S, C] bool
    pred_mask: np.ndarray  # [S, P] bool
    active: np.ndarray  # [S] bool
    num_rows: int
    cost_spent: float
    attributed: np.ndarray  # [S]
    quarantined: np.ndarray  # [P, F] bool


def entropy(p, r: Precision = F32):
    p = np.clip(p, 0.0, 1.0)

    def xlog2x(x):
        return np.where(x > 0, r(r(x * r(np.log(np.maximum(x, 1e-38)))) / LN2), 0.0)

    return r(-(r(xlog2x(p) + xlog2x(r(1.0 - p)))))


def combine(m: Model, func_probs, exec_mask, r: Precision = F32):
    """[C, P] predicate probabilities (paper Eq. 1, masked logistic pooling)."""
    e = exec_mask.astype(np.float32)
    p = np.clip(func_probs, 1e-6, 1.0 - 1e-6)
    logit = r(r(np.log(p)) - r(np.log1p(-p)))
    terms = r(r(logit * e) * m.weights)
    denom = np.maximum(r(np.sum(r(e * m.weights), axis=-1)), 1e-9)
    n_exec = np.sum(e, axis=-1)
    pooled = r(r(np.sum(terms, axis=-1)) / denom)
    sharp = r(np.power(np.maximum(n_exec, 1.0), m.rho))
    z = r(pooled * sharp + m.bias)
    out = r(1.0 / r(1.0 + r(np.exp(-z))))
    return np.where(n_exec > 0, out, np.float32(m.prior))


def derive(m: Model, s: State, r: Precision = F32):
    """-> (pred [C, P], entropy [C, P], joint [S, C]) before storage
    rounding, at precision ``r``."""
    pred = combine(m, s.func_probs, s.exec_mask, r)
    c = pred.shape[0]
    row_valid = np.arange(c) < s.num_rows
    joint = np.ones((s.pred_mask.shape[0], c), np.float32)
    for p in range(pred.shape[1]):
        joint = r(np.where(s.pred_mask[:, p:p + 1], joint * pred[None, :, p], joint))
    joint = np.where(s.active[:, None] & row_valid[None, :], joint, 0.0)
    return pred, entropy(pred, r), joint


def expected_f_curve(sorted_desc, alpha: float, r: Precision = F32):
    """E(F_alpha) of every prefix of a descending joint vector (Eq. 6)."""
    cs = r(np.cumsum(sorted_desc, dtype=np.float64))
    m = np.arange(1, sorted_desc.shape[0] + 1, dtype=np.float32)
    return r(r((1.0 + alpha) * cs) / r(alpha * cs[-1] + m))


def select(joint_row, alpha: float, r: Precision = F32):
    """Exact Theorem-1 selection over one slot -> (mask, expected F)."""
    sd = -np.sort(-joint_row)
    curve = expected_f_curve(sd, alpha, r)
    m_star = int(np.argmax(curve))
    thr = sd[m_star]
    above = joint_row > thr
    need = m_star + 1 - int(above.sum())
    equal = joint_row == thr
    eq_rank = np.cumsum(equal) - 1
    return above | (equal & (eq_rank < need)), float(curve[m_star])


def expected_f_of(joint_row, mask, alpha: float) -> float:
    """E(F_alpha) of an answer set (Eq. 6), in float64."""
    j = joint_row.astype(np.float64)
    s = float(j[mask].sum())
    return (1.0 + alpha) * s / (alpha * float(j.sum()) + max(int(mask.sum()), 1))


def pack_state(bits) -> np.ndarray:
    f = bits.shape[-1]
    return np.sum(bits.astype(np.int32) << np.arange(f, dtype=np.int32), axis=-1)


def benefits(m: Model, s: State, slot: int, shared: dict, r: Precision = F32):
    """Eq. 11 for one slot over [C, P] -> (benefit, next function)."""
    joint = s.joint[slot][:, None]  # stored, [C, 1]
    pred = s.pred_prob
    best = np.full(pred.shape, -np.inf, np.float32)
    best_fn = np.full(pred.shape, -1, np.int32)
    for f, (finite, p_hat) in enumerate(shared["per_fn"]):
        est = np.where(pred > 0, r(r(joint / np.maximum(pred, 1e-12)) * p_hat), 0.0)
        est = np.clip(est, 0.0, 1.0)
        ben = np.where(finite, r(r(joint * est) / np.maximum(m.costs[None, :, f], 1e-9)), -np.inf)
        better = ben > best
        best = np.where(better, ben, best)
        best_fn = np.where(better, f, best_fn)
    valid = (best_fn >= 0) & s.pred_mask[slot][None, :] & shared["row_valid"][:, None]
    valid &= bool(s.active[slot])
    benefit = np.where(valid, best, -np.inf).astype(np.float32)
    # candidates: outside the answer, or uncertain (masked median, floor)
    pm = s.pred_mask[slot]
    mean_h = r(np.sum(np.where(pm[None, :], s.uncertainty, 0.0), axis=-1) / max(int(pm.sum()), 1))
    rv = shared["row_valid"]
    srt = np.sort(np.where(rv, mean_h, np.inf))
    nv = max(int(rv.sum()), 1)
    med = r((srt[(nv - 1) // 2] + srt[nv // 2]) / 2)
    cand = ~s.in_answer[slot] | (mean_h >= np.maximum(med, np.float32(m.entropy_floor)))
    restricted = np.where(cand[:, None], benefit, -np.inf)
    n_ok = int(np.isfinite(restricted).sum())
    if n_ok >= min(m.plan_size, int(np.isfinite(benefit).sum())):
        benefit = restricted
    return benefit, best_fn


def top_k(values: np.ndarray, k: int) -> np.ndarray:
    """Indices of the k largest values, descending, ties by lowest index."""
    k = min(k, values.size)
    kth = np.partition(values, values.size - k)[values.size - k]
    gt = np.flatnonzero(values > kth)
    eq = np.flatnonzero(values == kth)[: k - gt.size]
    idx = np.concatenate([gt, eq])
    return idx[np.lexsort((idx, -values[idx]))]


def epoch(m: Model, s: State, execute, r: Precision = F32):
    """One epoch from state ``s`` -> (next state, executed [K, 3] triples,
    epoch spend, per-slot attribution, per-slot expected F).

    ``execute(obj, pred, fn) -> [K] probabilities`` is the bank."""
    c, p = s.pred_prob.shape
    row_valid = np.arange(c) < s.num_rows
    sid = pack_state(s.exec_mask) | pack_state(s.quarantined)[None, :]
    bins = np.clip(np.floor(np.clip(s.uncertainty, 0.0, 1.0 - 1e-7) * m.delta_h_all.shape[2]).astype(np.int32),
                   0, m.delta_h_all.shape[2] - 1)
    dh_all = m.delta_h_all[np.arange(p)[None, :], sid, bins]  # [C, P, F]
    h = entropy(s.pred_prob, r)
    per_fn = []
    for f in range(dh_all.shape[-1]):
        dh = dh_all[..., f]
        finite = np.isfinite(dh)
        h_hat = np.clip(r(h + np.where(finite, dh, 0.0)), 0.0, 1.0)
        x = r(h_hat * (LUT.size - 1))
        lo = np.floor(x)
        frac = r(x - lo)
        lo = np.minimum(lo, LUT.size - 1).astype(np.int32)
        hi = np.minimum(lo + 1, LUT.size - 1)
        p_hat = r(r(LUT[lo] * r(1.0 - frac)) + r(LUT[hi] * frac))
        per_fn.append((finite, p_hat))
    shared = dict(per_fn=per_fn, row_valid=row_valid)

    # per-slot plans, then the dedup merge with each triple's wanters
    wants: dict = {}
    for slot in np.flatnonzero(s.active):
        ben, fn = benefits(m, s, int(slot), shared, r)
        flat = ben.reshape(-1)
        idx = top_k(flat, m.plan_size)
        ok = np.isfinite(flat[idx])
        obj, prd = idx[ok] // p, idx[ok] % p
        for o, q, f in zip(obj.tolist(), prd.tolist(), fn[obj, prd].tolist()):
            wants.setdefault((o, q, f), []).append(int(slot))
    triples = np.asarray(sorted(wants), np.int64).reshape(-1, 3)
    o, q, f = triples[:, 0], triples[:, 1], triples[:, 2]

    charge = ~s.exec_mask[o, q, f]
    cost = m.costs[q, f].astype(np.float64)
    spend = float(np.sum(np.where(charge, cost, 0.0)))
    attributed = np.zeros(s.active.shape[0], np.float64)
    for i, key in enumerate(map(tuple, triples.tolist())):
        if charge[i]:
            for slot in wants[key]:
                attributed[slot] += cost[i] / len(wants[key])

    fp = s.func_probs.copy()
    em = s.exec_mask.copy()
    if triples.shape[0]:
        fp[o, q, f] = store(execute(o, q, f))
        em[o, q, f] = True
    nxt = dataclasses.replace(
        s, func_probs=fp, exec_mask=em, cost_spent=s.cost_spent + spend,
        attributed=s.attributed + attributed,
    )
    pred, unc, joint = derive(m, nxt, r)
    nxt.pred_prob, nxt.uncertainty, nxt.joint = store(pred), store(unc), store(joint)
    ans = np.zeros_like(s.in_answer)
    ef = np.zeros(s.active.shape[0])
    for slot in np.flatnonzero(s.active):
        mask, ef[slot] = select(nxt.joint[slot], m.alpha, r)
        ans[slot] = mask & row_valid
    nxt.in_answer = ans
    return nxt, triples, spend, attributed, ef
