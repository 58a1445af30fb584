"""The comparison that decides ``correct``.

For chunks sampled from the window (drawn from the seed), the harness keeps
the session state the chunk started from and the one it returned.  Once the
window has closed, the plain reference (``bench/reference``) replays the
chunk's epochs from the same starting state, and each number below is held
to its limit from the configuration file:

* ``plan_gap``: the largest of three shares (Eq. 11 scoring, top-k,
  candidates, dedup merge, write-once charging, ledger attribution): the
  enrichment triples executed in the chunk by one side and not the other,
  over the reference's; the gap in the chunk's charged cost; and the largest
  gap in a tenant's fair share of it, both over the chunk's spend;
* ``output_gap`` (a bank of pre-materialised outputs): largest distance
  between a value the chunk wrote into the substrate and the benchmark's own
  copy of the corpus for that triple; exact;
* ``trunk_gap`` (a model bank): mean distance, over a sample of the model
  triples the chunk executed drawn from the seed, between the value the
  chunk stored and the reference forward's (``bench/reference/qwen3``, in
  float32 from the benchmark's bfloat16 weights), both at storage precision.
  The mean, not the largest: rounding to storage hides a gap under one ulp
  in any one value, but the share of values it moves by an ulp is the gap,
  so the mean over many triples reads it;
* ``derive_miss``: share of stored derived values on enriched rows (predicate
  probability, entropy, each active slot's joint) that differ from the
  reference's derive of the returned substrate, rounded to storage;
* ``answer_gap``: per active slot, how far the returned answer set's expected
  F falls below the Theorem-1 optimum for the returned joint, or the epoch's
  reported expected F departs from it (relative);
* ``rows_wrong`` (cells with a stream): rows of the session's output buffer
  that differ from the rows the stream sent, in order, plus any difference
  in the row count.  Exact: every row lands once, in order.
"""

from __future__ import annotations

import numpy as np

from bench.reference import pique


def host_state(st) -> pique.State:
    """A device ``SessionState`` -> the reference's host ``State``."""
    import jax

    sub, der = st.substrate, st.derived
    g = jax.device_get(
        (sub.func_probs, sub.exec_mask, der.pred_prob, der.uncertainty,
         der.joint_prob, der.in_answer, st.pred_mask, st.active, st.num_rows,
         sub.cost_spent, st.ledger.attributed, st.quarantined)
    )
    f32 = lambda x: np.asarray(x).astype(np.float32)  # noqa: E731
    return pique.State(
        func_probs=f32(g[0]), exec_mask=np.asarray(g[1]), pred_prob=f32(g[2]),
        uncertainty=f32(g[3]), joint=f32(g[4]), in_answer=np.asarray(g[5]),
        pred_mask=np.asarray(g[6]), active=np.asarray(g[7]), num_rows=int(g[8]),
        cost_spent=float(g[9]), attributed=np.asarray(g[10], np.float64),
        quarantined=np.asarray(g[11]),
    )


def model_of(cfg: dict, ref: dict) -> pique.Model:
    t = ref["tables"]
    return pique.Model(
        weights=np.asarray(t["weights"], np.float32),
        bias=np.asarray(t["bias"], np.float32),
        rho=np.asarray(t["rho"], np.float32),
        delta_h_all=np.asarray(t["delta_h_all"], np.float32),
        costs=np.asarray(ref["costs"], np.float32),
        plan_size=cfg["plan_size"], prior=cfg["prior"], alpha=cfg["alpha"],
    )


def replay(model, pre: pique.State, execute, epochs: int, r=pique.F32):
    """The reference's run of ``epochs`` epochs from ``pre`` -> (state,
    per-slot expected F of the last epoch)."""
    s, ef = pre, None
    for _ in range(epochs):
        s, _, _, _, ef = pique.epoch(model, s, execute, r)
    return s, ef


def optimum_ef(joint_row) -> float:
    """Theorem-1 optimum of expected F1 over prefixes, in float64."""
    j = np.sort(joint_row.astype(np.float64))[::-1]
    cs = np.cumsum(j)
    m = np.arange(1, j.size + 1, dtype=np.float64)
    return float(np.max(2.0 * cs / (cs[-1] + m)))


def new_triples(pre, post, sample=None, rng=None):
    """(obj, pred, fn) of the triples ``post`` executed since ``pre``; at
    most ``sample`` of them, drawn by ``rng``, where ``sample`` is set."""
    o, q, f = np.nonzero(post.exec_mask & ~pre.exec_mask)
    if sample is not None and o.size > sample:
        pick = np.sort(rng.choice(o.size, size=sample, replace=False))
        o, q, f = o[pick], q[pick], f[pick]
    return o, q, f


def trunk_gap(pre, post, value, sample: int, rng, weights=None) -> float:
    """``trunk_gap`` of the model triples ``post`` executed since ``pre``
    (``sample`` of them, drawn by ``rng``): against the stored values, or,
    with ``weights`` set, against the reference run at those weights in the
    program's place (the control)."""
    o, q, f = new_triples(pre, post, sample, rng)
    if not o.size:
        return 0.0
    want = pique.store(value(o, q, f))
    got = post.func_probs[o, q, f] if weights is None else pique.store(value(o, q, f, weights=weights))
    return float(np.mean(np.abs(got - want)))


def compare(model, pre, post, ref, reported_ef, bank_value=None) -> dict:
    """The numbers of one sampled chunk.  ``post`` is the side under test
    (the program, or the control), ``ref`` the reference's replay, and
    ``reported_ef`` the expected F the side reported for the chunk's last
    epoch.  ``bank_value(obj, pred, fn)``, where given, is the bank's true
    output, checked exactly on every executed triple (``output_gap``)."""
    new_p = post.exec_mask & ~pre.exec_mask
    new_r = ref.exec_mask & ~pre.exec_mask
    n_ref = int(new_r.sum())
    plan_miss = float(np.sum(new_p ^ new_r)) / max(n_ref, 1)
    out = {}
    if bank_value is not None:
        o, q, f = new_triples(pre, post)
        out["output_gap"] = float(np.max(np.abs(
            post.func_probs[o, q, f] - pique.store(bank_value(o, q, f))))) if o.size else 0.0

    floor = float(np.min(model.costs))
    spend_ref = max(ref.cost_spent - pre.cost_spent, floor)
    spend_gap = abs((post.cost_spent - pre.cost_spent) - (ref.cost_spent - pre.cost_spent)) / spend_ref
    ledger_gap = float(np.max(np.abs(
        (post.attributed - pre.attributed) - (ref.attributed - pre.attributed)
    ))) / spend_ref

    pred, unc, joint = pique.derive(model, post)
    rows = np.flatnonzero(post.exec_mask.any(axis=(1, 2)))
    act = np.flatnonzero(post.active)
    differ = (
        np.sum(post.pred_prob[rows] != pique.store(pred)[rows])
        + np.sum(post.uncertainty[rows] != pique.store(unc)[rows])
        + np.sum(post.joint[np.ix_(act, rows)] != pique.store(joint)[np.ix_(act, rows)])
    )
    total = rows.size * (2 * pred.shape[1] + act.size)
    derive_miss = float(differ) / max(total, 1)

    row_valid = np.arange(post.joint.shape[1]) < post.num_rows
    answer_gap = 0.0
    for s in range(post.active.shape[0]):
        if not post.active[s]:
            if post.in_answer[s].any():
                answer_gap = max(answer_gap, 1.0)
            continue
        if (post.in_answer[s] & ~row_valid).any():
            answer_gap = max(answer_gap, 1.0)
        opt = optimum_ef(post.joint[s])
        got = pique.expected_f_of(post.joint[s], post.in_answer[s], model.alpha)
        gap = max(opt - got, abs(float(reported_ef[s]) - opt)) / max(opt, 1e-12)
        answer_gap = max(answer_gap, gap)
    out.update(plan_gap=max(plan_miss, spend_gap, ledger_gap), derive_miss=derive_miss,
               answer_gap=answer_gap, plan_miss=plan_miss, spend_gap=spend_gap,
               ledger_gap=ledger_gap)
    return out


def check_samples(bundle: dict, cfg: dict, samples: list, seed: int, control: bool):
    """The worst readings over the sampled chunks -> (program numbers,
    control numbers or None).  The control is the reference one precision
    below the configuration's, put in the program's place on the same
    chunks: the planner in bfloat16 (the configuration states float32
    arithmetic) and, with a model bank, the model level at the weights
    named by ``trunk_control`` (the configuration states bfloat16)."""
    model = model_of(cfg, bundle["reference"])
    low_p = pique.Precision("bfloat16")
    readings, ctrl = [], []
    for s in samples:
        pre, post = s["pre"], s["post"]
        if "model_value" in bundle:
            # a model bank: the replay takes the chunk's own model outputs
            # (lanes it did not run read the prior); ``trunk_gap`` holds
            # those outputs to the reference forward
            def replay_bank(o, q, f, post=post):
                return np.where(post.exec_mask[o, q, f], post.func_probs[o, q, f], cfg["prior"])

            exact = None
        else:
            def replay_bank(o, q, f):
                return bundle["corpus"][o, q, f]

            exact = replay_bank
        ref, _ = replay(model, pre, replay_bank, cfg["chunk_size"])
        r = compare(model, pre, post, ref, s["ef"], exact)
        if exact is None:
            r["trunk_gap"] = trunk_gap(pre, post, bundle["model_value"], cfg["trunk_sample"],
                                       np.random.default_rng(seed))
        readings.append(r)
        if control:
            low, low_ef = replay(model, pre, replay_bank, cfg["chunk_size"], low_p)
            c = compare(model, pre, low, ref, low_ef, exact)
            if exact is None:
                for w in ("int8", "fp8"):
                    c[f"trunk_gap_{w}"] = trunk_gap(pre, post, bundle["model_value"],
                                                    cfg["trunk_sample"],
                                                    np.random.default_rng(seed), weights=w)
                c["trunk_gap"] = c[f"trunk_gap_{cfg['trunk_control']}"]
            ctrl.append(c)
    return worst(readings), (worst(ctrl) if control else None)


def rows_wrong(bank_rows, num_rows: int, corpus, expected_rows: int) -> int:
    """Rows of the session's output buffer that are not the corpus rows the
    stream sent, in order, plus the miscount of rows."""
    n = min(num_rows, expected_rows)
    bad = np.any(np.asarray(bank_rows[:n]) != np.asarray(corpus[:n]), axis=(1, 2))
    return int(bad.sum()) + abs(int(num_rows) - int(expected_rows))


def worst(readings: list) -> dict:
    """The largest reading of each number over the sampled chunks."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit; correct when none exceeds its limit
    (and none is missing or not finite)."""
    table = {}
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name)
        fine = v is not None and np.isfinite(v) and v <= limit
        ok &= bool(fine)
        table[name] = {"value": None if v is None else float(v), "limit": float(limit)}
    return ok, table
