"""Assigned input shapes (the 4 LM-family cells) + per-arch applicability.

    train_4k     seq 4096,   global batch 256   -> train_step
    prefill_32k  seq 32768,  global batch 32    -> serve prefill
    decode_32k   KV 32768,   global batch 128   -> serve decode (1 new token)
    long_500k    KV 524288,  global batch 1     -> long-context decode

``long_500k`` runs only for sub-quadratic archs (cfg.subquadratic); pure
full-attention archs skip it (DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import dataclasses

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str  # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


def shape_applicable(cfg: ModelConfig, shape: str) -> tuple[bool, str]:
    """-> (runnable, reason-if-skipped)."""
    if shape == "long_500k" and not cfg.subquadratic:
        return False, (
            "skip: pure full-attention arch — 500k context requires a "
            "sub-quadratic path (DESIGN.md §Arch-applicability)"
        )
    return True, ""


def smoke_shape(spec: ShapeSpec) -> ShapeSpec:
    """Tiny same-kind shape for CPU smoke tests."""
    return ShapeSpec(spec.name + "-smoke", spec.kind,
                     seq_len=64 if spec.kind != "decode" else 64,
                     global_batch=2)


def all_cells():
    """Every (arch x shape) cell, with applicability flags."""
    from repro.configs.archs import ARCHS

    cells = []
    for arch, fn in ARCHS.items():
        cfg = fn()
        for sname, spec in SHAPES.items():
            ok, reason = shape_applicable(cfg, sname)
            cells.append(dict(arch=arch, shape=sname, runnable=ok, reason=reason))
    return cells
