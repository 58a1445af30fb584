"""The model level at the smoke size of its architecture: the numpy Qwen3
reference against the program's trunk, and the int8 and fp8 steps below
it."""

from __future__ import annotations

import math
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

def _smoke_trunk(rng):
    L, d, H, KV, hd, ff = 2, 64, 4, 2, 16, 128

    def m(shape, fan):
        return (rng.standard_normal(shape) / math.sqrt(fan)).astype(np.float32)

    def v(shape):
        return (1.0 + 0.1 * rng.standard_normal(shape)).astype(np.float32)

    return {"ln1": v((L, d)), "ln2": v((L, d)),
            "attn": {"wq": m((L, d, H, hd), d), "wk": m((L, d, KV, hd), d),
                     "wv": m((L, d, KV, hd), d), "wo": m((L, H, hd, d), H * hd),
                     "q_norm": v((L, hd)), "k_norm": v((L, hd))},
            "mlp": {"wg": m((L, d, ff), d), "wu": m((L, d, ff), d), "wd": m((L, ff, d), ff)}}


def _ref_layers(t, quant=None):
    from bench.reference import qwen3

    f = {None: lambda x, n: np.asarray(x, np.float32), "int8": qwen3.quantize,
         "fp8": qwen3.quantize_fp8}[quant]
    for i in range(t["ln1"].shape[0]):
        a, m = t["attn"], t["mlp"]
        yield dict(ln1=t["ln1"][i], ln2=t["ln2"][i], q_norm=a["q_norm"][i], k_norm=a["k_norm"][i],
                   wq=f(a["wq"][i], 1), wk=f(a["wk"][i], 1), wv=f(a["wv"][i], 1),
                   wo=f(a["wo"][i], 2), wg=f(m["wg"][i], 1), wu=f(m["wu"][i], 1),
                   wd=f(m["wd"][i], 1))


def test_numpy_trunk_matches_the_program_and_int8_departs():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from bench.reference import qwen3
    from repro.configs.archs import get_config
    from repro.enrich import cascade

    rng = np.random.default_rng(0)
    cfg = dataclasses.replace(get_config("qwen3-1.7b", smoke=True), dtype="float32")
    t = _smoke_trunk(rng)
    feats = rng.standard_normal((6, 8)).astype(np.float32)
    head = {"proj": (0.3 * rng.standard_normal((8, 64))).astype(np.float32),
            "out": (0.05 * rng.standard_normal((64, 1))).astype(np.float32)}
    with jax.default_matmul_precision("highest"):
        prog = np.asarray(cascade._backbone_apply(
            cfg, {"layers": (jax.tree.map(jnp.asarray, t),)}, head, jnp.asarray(feats)))
    arch = dict(rms_norm_eps=1e-6, rope_theta=1e6)
    b = feats.shape[0]
    ref = qwen3.tag(feats, np.broadcast_to(head["proj"], (b, 8, 64)),
                    np.broadcast_to(head["out"][:, 0], (b, 64)), _ref_layers(t), arch, 8)
    assert np.max(np.abs(prog - ref)) < 1e-5
    for step in ("int8", "fp8"):
        low = qwen3.tag(feats, np.broadcast_to(head["proj"], (b, 8, 64)),
                        np.broadcast_to(head["out"][:, 0], (b, 64)), _ref_layers(t, step), arch, 8)
        assert np.max(np.abs(low - ref)) > 1e-4, step


def test_fp8_step_rounds_to_three_mantissa_bits_per_channel():
    from bench.reference import qwen3

    rng = np.random.default_rng(1)
    w = rng.standard_normal((64, 5)).astype(np.float32) * np.array([1e-3, 0.1, 1.0, 10.0, 0.0],
                                                                   np.float32)
    q = qwen3.quantize_fp8(w)
    scale = np.max(np.abs(w), axis=0) / 448.0
    assert np.all(q[:, 4] == 0.0)
    assert np.allclose(np.max(np.abs(q[:, :4]), axis=0), 448.0 * scale[:4], rtol=1e-6)
    # a normal e4m3 value is off by at most half of its 2**-3 mantissa step
    big = np.abs(w[:, :4]) >= 2.0 ** -6 * scale[:4]
    rel = np.abs(q[:, :4] - w[:, :4]) / np.abs(w[:, :4])
    assert np.all(rel[big] <= 2.0 ** -4 + 1e-6)
    assert np.mean(rel[big]) > 2.0 ** -8  # coarser than bfloat16
