"""Fused PIQUE benefit-scoring Pallas TPU kernels (the paper's plan-generation
hot loop, DESIGN.md section 6).

Per tile of (object, predicate) pairs, computes in ONE HBM pass what the jnp
reference does in ~6 (bin -> decision-table lookup -> inverse entropy ->
joint update -> Eq. 11 benefit):

    bin      = floor(u * BINS)      u: the stored uncertainty
    delta    = table_delta[pred, state, bin]        (one-hot gather)
    fn       = table_next [pred, state, bin]        (one-hot gather)
    h_hat    = clip(h + delta, 0, 1)  h: H(p), recomputed in f32 by the caller
    p_hat    = LUT(h_hat)  upper entropy root       (one-hot gather, lerp)
    est_j    = clip(joint / p * p_hat, 0, 1)        (conjunctive fast path)
    cost     = costs[pred, fn]                      (one-hot gather)
    benefit  = joint * est_j / cost                 (Eq. 11)

**Tiling.**  Every [R, LANES] operand is cut into blocks of ``BLOCK_ROWS``
(16) rows x 128 lanes, a shape the TPU lowering accepts for 32-bit and
packed 16-bit operands alike.  The kernel body upcasts the block into an
f32 VMEM scratch and walks it one lane-dense [1, 128] row at a time in a
``fori_loop``, so every gather works on a row vector and no value is ever
reshaped across sublanes and lanes.

**Two-level one-hot gather.**  Dynamic vector gathers are weak on the TPU
VPU, so a lookup ``table[idx]`` is rendered as matmuls on one-hot masks.  A
flat one-hot over a 4096-bin LUT would cost a [4096, 128] mask per row;
instead the index splits as ``idx = 64 * a + b``.  One MXU matmul of the
table laid out as [64 (b), A (a)] against the one-hot of ``a`` ([A, 128])
fetches each lane's whole 64-entry table row, and a masked sublane sum over
``b`` picks the entry.  The matmul runs at ``Precision.HIGHEST`` (f32
contraction), which is exact against a 0/1 mask.  Several tables sharing
one index (delta and next function; the LUT and its one-step-shifted copy
for the lerp's upper neighbour) ride in one matmul.

Two grid layouts share the row math:

* single-query ``enrich_score_tiles`` — grid (R / BLOCK_ROWS,);
* batched multi-query ``enrich_score_tiles_batched`` /
  ``enrich_score_best_tiles_batched`` — grid (Q, R / BLOCK_ROWS): the
  substrate-derived rows (pred_prob / uncertainty / entropy / state / pred
  idx) are stored ONCE at [R, LANES] and re-blocked for every query by the
  index map, so the HBM footprint of shared state never grows with Q; only
  joint and the outputs carry a [Q, ...] axis.

The ``best`` variant additionally fuses the beyond-paper per-function
benefit argmax over F *inside* the tile: the per-function deltas and costs
come out of one gather each as F rows, and the Eq. 11 argmax runs in
registers, so no [Q, N, P, F] tensor ever exists.

**Dequant-in-tile:** the probability operands (pred_prob / uncertainty /
joint) may arrive at the substrate's STORAGE dtype — bf16 under the
million-row substrate — and the kernel body's first touch of those blocks
is ``.astype(jnp.float32)``: all scoring math runs in f32 and outputs are
f32.  Since bf16 -> f32 is exact, a bf16-fed kernel computes on bitwise-
identical inputs to one fed pre-upcast f32 copies.  Index-like operands
(state id, predicate idx, candidate mask) and the entropy row stay f32.

**Exactness against the jnp reference.**  The gathers are exact, the
entropy row is the reference's own ``binary_entropy(pred_prob)``, and every
arithmetic step is the reference's expression in the reference's order
(``repro.core.benefit.compute_benefits_batched``), so the two agree
wherever the compilers round each f32 operation alike.  Jitted as the
superstep runs them, the interpreted kernel and the reference agree bit
for bit on every output (``test_enrich_score_batched_bitwise_with_
reference_under_jit``), and ``chip_smoke.py`` requires the compiled
kernel's session to reproduce the jnp session's answer digest and spend
bit for bit on the chip.  Nothing forbids a compiler from contracting
``a*b + c`` into an FMA differently in Mosaic and XLA (the LUT lerp, the
best-mode chain), so the tests of unjitted calls pin ``benefit`` /
``est_joint`` within ``KERNEL_RTOL`` (relative, with the same absolute
floor) and ``next_fn`` / ``cost`` equal wherever the benefit is finite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# delta_h_all stores +inf where a function is already executed / unlearnable.
# inf poisons one-hot matmul gathers (0 * inf = nan), so hosts sanitize the
# table to this sentinel and the kernel tests against BIG_INVALID / 2.
BIG_INVALID = 1e9

LANES = 128  # lane width of every row block
BLOCK_ROWS = 16  # rows per block: legal for f32 and packed bf16 operands
SPLIT = 64  # minor radix of the two-level gather index (idx = SPLIT*a + b)
N_ROWS_IN = 7  # operand rows per block: pp, unc, ent, state, pred, joint, cand

# kernel-vs-reference tolerance of the ops-level tests (module docstring)
KERNEL_RTOL = 5e-3  # per-triple benefit / est_joint


def gather_table(tables):
    """Stage f32 tables [n, K] that share one index for ``_gather``.

    -> [n * SPLIT, A] f32 with A the index's major radix padded to a lane
    multiple; entry [t * SPLIT + b, a] is ``tables[t, SPLIT * a + b]``.
    Entries must be finite.
    """
    n, k = tables.shape
    a = -(-k // SPLIT)
    a_pad = -(-a // LANES) * LANES
    t = jnp.pad(jnp.asarray(tables, jnp.float32), ((0, 0), (0, a_pad * SPLIT - k)))
    return t.reshape(n, a_pad, SPLIT).transpose(0, 2, 1).reshape(n * SPLIT, a_pad)


def _gather(idx, tab_ref):
    """Exact ``[table[idx] for table in staged tables]``; idx: [1, L] f32.

    ``tab_ref`` holds ``gather_table`` output.  Returns one [1, L] f32 row
    per staged table.
    """
    rows_total, a_size = tab_ref.shape
    width = idx.shape[-1]
    ii = idx.astype(jnp.int32)
    a = ii // SPLIT
    b = ii - SPLIT * a
    onehot_a = (
        jax.lax.broadcasted_iota(jnp.int32, (a_size, width), 0) == a
    ).astype(jnp.float32)
    rows = jax.lax.dot_general(
        tab_ref[...], onehot_a, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [n_tab * SPLIT, L]: each lane's table row
    pick_b = jax.lax.broadcasted_iota(jnp.int32, (SPLIT, width), 0) == b
    return [
        jnp.sum(
            jnp.where(pick_b, rows[t * SPLIT:(t + 1) * SPLIT], 0.0),
            axis=0, keepdims=True,
        )
        for t in range(rows_total // SPLIT)
    ]


def _lut_lerp(h_hat, lut_ref, lut_bins: int):
    """Inverse-entropy upper root via LUT gather + linear interpolation.

    ``lut_ref`` stages (lut, lut shifted one bin) so both lerp neighbours
    come out of one gather at ``lo``.
    """
    x = h_hat * (lut_bins - 1)
    lo = jnp.floor(x)
    frac = x - lo
    p_lo, p_hi = _gather(lo, lut_ref)
    return p_lo * (1.0 - frac) + p_hi * frac


def _score_table_row(
    u, h, p, joint, state, pred, cand,  # each [1, L] f32
    delta_next_ref, cost_ref, lut_ref,
    *,
    num_bins: int, num_states: int, num_functions: int, lut_bins: int,
):
    """Paper decision-table scoring for one row -> (benefit, fn, est_joint)."""
    bin_f = jnp.floor(jnp.clip(u, 0.0, 1.0 - 1e-7) * num_bins)
    flat = pred * (num_states * num_bins) + state * num_bins + bin_f  # [1, L]
    delta, fn = _gather(flat, delta_next_ref)

    h_hat = jnp.clip(h + delta, 0.0, 1.0)
    p_hat = _lut_lerp(h_hat, lut_ref, lut_bins)

    est_joint = jnp.where(p > 0, joint / jnp.maximum(p, 1e-12) * p_hat, 0.0)
    est_joint = jnp.clip(est_joint, 0.0, 1.0)

    cost_idx = pred * num_functions + jnp.maximum(fn, 0.0)
    (cost,) = _gather(cost_idx, cost_ref)
    cost = jnp.maximum(cost, 1e-9)

    valid = (fn >= 0.0) & (cand > 0.0)
    benefit = jnp.where(valid, joint * est_joint / cost, NEG_INF)
    return benefit, fn, est_joint


def _score_best_row(
    u, h, p, joint, state, pred, cand,  # each [1, L] f32
    delta_all_ref,  # staged F tables over P*S*B, +inf sanitized to BIG_INVALID
    cost_ref,  # staged F tables over P
    lut_ref,
    *,
    num_bins: int, num_states: int, num_functions: int, lut_bins: int,
):
    """Fused best-benefit function selection: Eq. 11 argmax over F in-registers.

    One gather fetches ALL per-function deltas for the row (and one all
    per-function costs); the per-function loop below is a static unroll over
    [1, L] rows, so nothing F-shaped is ever written back to HBM.
    """
    width = h.shape[-1]
    bin_f = jnp.floor(jnp.clip(u, 0.0, 1.0 - 1e-7) * num_bins)
    base = pred * (num_states * num_bins) + state * num_bins + bin_f  # [1, L]
    deltas = _gather(base, delta_all_ref)  # F x [1, L]
    costs = _gather(pred, cost_ref)  # F x [1, L]

    best_ben = jnp.full((1, width), NEG_INF, jnp.float32)
    best_fn = jnp.full((1, width), -1.0, jnp.float32)
    best_ej = jnp.zeros((1, width), jnp.float32)
    for f in range(num_functions):  # static unroll; F is 3-4
        delta_f = deltas[f]
        invalid_f = delta_f > BIG_INVALID / 2
        h_hat = jnp.clip(h + jnp.where(invalid_f, 0.0, delta_f), 0.0, 1.0)
        p_hat = _lut_lerp(h_hat, lut_ref, lut_bins)
        est_j = jnp.where(p > 0, joint / jnp.maximum(p, 1e-12) * p_hat, 0.0)
        est_j = jnp.clip(est_j, 0.0, 1.0)
        cost_f = jnp.maximum(costs[f], 1e-9)
        ben_f = jnp.where(invalid_f, NEG_INF, joint * est_j / cost_f)
        better = ben_f > best_ben  # strict: ties keep the FIRST max (argmax)
        best_ben = jnp.where(better, ben_f, best_ben)
        best_fn = jnp.where(better, float(f), best_fn)
        best_ej = jnp.where(better, est_j, best_ej)

    valid = (best_fn >= 0.0) & (cand > 0.0)
    benefit = jnp.where(valid, best_ben, NEG_INF)
    return benefit, best_fn, best_ej


# ------------------------------------------------------------ kernel bodies --


def _score_block(row_fn, inputs, out_refs, rows_ref, tables):
    """Score one [BLOCK_ROWS, L] block row by row.

    ``inputs`` are the operand blocks (uncertainty, entropy, pred_prob,
    joint, state, pred, cand) at any dtype; they are upcast once into the
    f32 scratch ``rows_ref`` [N_ROWS_IN, BLOCK_ROWS, L], from which a
    ``fori_loop`` reads one [1, L] row at a time (a dynamic row read of a
    packed bf16 block is not expressible, of the f32 scratch it is).
    Outputs land row by row in ``out_refs`` (benefit, next_fn, est_joint).
    """
    for i, x in enumerate(inputs):
        rows_ref[i] = x.astype(jnp.float32)

    def body(r, carry):
        rows = [rows_ref[i, pl.ds(r, 1), :] for i in range(N_ROWS_IN)]
        outs = row_fn(*rows, *tables)
        for ref, val in zip(out_refs, outs):
            ref[pl.ds(r, 1), :] = val
        return carry

    jax.lax.fori_loop(0, BLOCK_ROWS, body, 0)


def _score_kernel(
    pred_prob_ref, unc_ref, ent_ref, state_ref, pred_ref, joint_ref, cand_ref,
    *rest,  # staged tables, 3 out refs, the f32 row scratch
    row_fn,
):
    tables, outs, rows_ref = rest[:-4], rest[-4:-1], rest[-1]
    inputs = (unc_ref, ent_ref, pred_prob_ref, joint_ref, state_ref, pred_ref,
              cand_ref)
    _score_block(row_fn, [x[...] for x in inputs], outs, rows_ref, tables)


def _score_kernel_batched(
    pred_prob_ref, unc_ref, ent_ref, state_ref, pred_ref,  # [BR, L] shared
    joint_ref,  # [1, BR, L] per-query rows
    *rest,  # staged tables, 3 [1, BR, L] out refs, the f32 row scratch
    row_fn,
):
    # Candidate/§4.1 masking is the batched caller's job (it needs global
    # reductions anyway), so no cand operand is streamed per query — validity
    # inside the tile is just "a next function exists".
    tables, outs, rows_ref = rest[:-4], rest[-4:-1], rest[-1]
    joint = joint_ref[0]
    inputs = [unc_ref[...], ent_ref[...], pred_prob_ref[...], joint,
              state_ref[...], pred_ref[...], jnp.ones(joint.shape, jnp.float32)]
    _score_block(row_fn, inputs, [o.at[0] for o in outs], rows_ref, tables)


# ------------------------------------------------------------- entry points --


def _full(arr, ndim_grid: int):
    zeros = (0,) * arr.ndim
    if ndim_grid == 1:
        return pl.BlockSpec(arr.shape, lambda i: zeros)
    return pl.BlockSpec(arr.shape, lambda qi, i: zeros)


def _row_scratch():
    return [pltpu.VMEM((N_ROWS_IN, BLOCK_ROWS, LANES), jnp.float32)]


def enrich_score_tiles(
    pred_prob, unc, ent, state_id, pred_idx, joint, cand,  # each [R, LANES]
    delta_next_tab, cost_tab, lut_tab,  # gather_table-staged tables
    *,
    num_bins: int,
    num_states: int,
    num_functions: int,
    lut_bins: int,
    interpret: bool = False,
):
    """Single-query decision-table scoring: grid (R / BLOCK_ROWS,)."""
    r, width = pred_prob.shape
    kernel = functools.partial(
        _score_kernel,
        row_fn=functools.partial(
            _score_table_row,
            num_bins=num_bins, num_states=num_states,
            num_functions=num_functions, lut_bins=lut_bins,
        ),
    )
    row_spec = pl.BlockSpec((BLOCK_ROWS, width), lambda i: (i, 0))
    tables = (delta_next_tab, cost_tab, lut_tab)
    return pl.pallas_call(
        kernel,
        grid=(r // BLOCK_ROWS,),
        in_specs=[row_spec] * N_ROWS_IN + [_full(t, 1) for t in tables],
        out_specs=[row_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((r, width), jnp.float32)] * 3,
        scratch_shapes=_row_scratch(),
        interpret=interpret,
        name="enrich_score_tiles",
    )(pred_prob, unc, ent, state_id, pred_idx, joint, cand, *tables)


def _batched_call(row_fn, name, shared, joint, tables, interpret):
    q = joint.shape[0]
    r, width = shared[0].shape
    shared_spec = pl.BlockSpec((BLOCK_ROWS, width), lambda qi, i: (i, 0))
    per_q = pl.BlockSpec((1, BLOCK_ROWS, width), lambda qi, i: (qi, i, 0))
    return pl.pallas_call(
        functools.partial(_score_kernel_batched, row_fn=row_fn),
        grid=(q, r // BLOCK_ROWS),
        in_specs=[shared_spec] * len(shared) + [per_q]
        + [_full(t, 2) for t in tables],
        out_specs=[per_q] * 3,
        out_shape=[jax.ShapeDtypeStruct((q, r, width), jnp.float32)] * 3,
        scratch_shapes=_row_scratch(),
        interpret=interpret,
        name=name,
    )(*shared, joint, *tables)


def enrich_score_tiles_batched(
    pred_prob, unc, ent, state_id, pred_idx,  # each [R, LANES], shared
    joint,  # [Q, R, LANES]
    delta_next_tab, cost_tab, lut_tab,  # gather_table-staged tables
    *,
    num_bins: int,
    num_states: int,
    num_functions: int,
    lut_bins: int,
    interpret: bool = False,
):
    """Multi-query decision-table scoring: grid (Q, R / BLOCK_ROWS)."""
    row_fn = functools.partial(
        _score_table_row,
        num_bins=num_bins, num_states=num_states,
        num_functions=num_functions, lut_bins=lut_bins,
    )
    return _batched_call(
        row_fn, "enrich_score_tiles_batched",
        (pred_prob, unc, ent, state_id, pred_idx), joint,
        (delta_next_tab, cost_tab, lut_tab), interpret,
    )


def enrich_score_best_tiles_batched(
    pred_prob, unc, ent, state_id, pred_idx,  # each [R, LANES], shared
    joint,  # [Q, R, LANES]
    delta_all_tab,  # gather_table of the F per-function delta columns
    cost_tab,  # gather_table of the F per-function cost columns over P
    lut_tab,  # gather_table(lut, lut shifted one bin)
    *,
    num_bins: int,
    num_states: int,
    num_functions: int,
    lut_bins: int,
    interpret: bool = False,
):
    """Multi-query fused best-mode scoring: Eq. 11 argmax over F inside the
    tile, so no [Q, N, P, F] intermediate ever reaches HBM."""
    row_fn = functools.partial(
        _score_best_row,
        num_bins=num_bins, num_states=num_states,
        num_functions=num_functions, lut_bins=lut_bins,
    )
    return _batched_call(
        row_fn, "enrich_score_best_tiles_batched",
        (pred_prob, unc, ent, state_id, pred_idx), joint,
        (delta_all_tab, cost_tab, lut_tab), interpret,
    )
