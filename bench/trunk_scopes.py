"""Device time of the model trunk's expert and conv layers, read under the
program's sub-scopes of ``pique/trunk`` (``pique/experts``, ``pique/conv``).

``bench/scopes.py`` puts an op down to the FIRST ``pique/`` scope of its op
name, which for the trunk's ops is ``bank``; this reads the sub-scope an
op's op name holds, among the ops of the computations that run the trunk.
XLA's TPU lowering of the experts' grouped matmuls (``jax.lax.ragged_dot``)
names those instructions ``ragged-dot-<n>`` in place of the op name the
program gave them, so an instruction so named counts under ``experts``.
"""

from __future__ import annotations

import sys

from bench import scopes, trace as trace_lib

GROUPED_MATMUL = "ragged-dot"


def _trunk_comps(mod) -> set:
    """Computations of ``mod`` that run the trunk: those holding an op whose
    op name holds ``pique/trunk`` (the branch and its layer scans)."""
    if not hasattr(mod, "trunk_comps"):
        mod.trunk_comps = {c for op_name, c in mod.instrs.values()
                           if scopes.SCOPE_PREFIX + "trunk" in op_name}
    return mod.trunk_comps


def part_of(mod, key):
    """The trunk layer an instruction belongs to: "experts", "conv", "trunk"
    (elsewhere in the trunk) or None (outside it).  By computation, not op
    name alone: an op of a rematerialised layer may name its scope without
    the scopes around it (``checkpoint/pique/experts/...``)."""
    op_name, comp = mod.instrs[key]
    if comp not in _trunk_comps(mod):
        return None
    if key[0].startswith(GROUPED_MATMUL) or scopes.SCOPE_PREFIX + "experts" in op_name:
        return "experts"
    if scopes.SCOPE_PREFIX + "conv" in op_name:
        return "conv"
    return "trunk"


def busy_s(sc, parts) -> float:
    """Device-busy seconds of the ops of ``parts`` in the window (union of
    their intervals), averaged over devices."""
    tot = 0
    for ops in sc.ops.values():
        iv = [(s, e) for _, s, e, mod, key, _ in ops
              if mod is not None and part_of(mod, key) in parts]
        tot += trace_lib.length(trace_lib.union(trace_lib.clip(iv, sc.r.w0, sc.r.w1)))
    return tot / max(len(sc.ops), 1) / 1e9


def per_trunk_run_ms(run, *parts):
    """Device ms of ``parts`` per execution of the trunk's branch, or None
    where the run has no such ops (a program without these scopes)."""
    sc = scopes.from_run(run)
    if sc is None:
        return None
    runs = sc.branch_runs("trunk")
    busy = busy_s(sc, parts)
    if not runs or busy <= 0:
        return None
    trunk = busy_s(sc, ("experts", "conv", "trunk"))
    print(f"[bench] trunk {trunk * 1e3 / runs:.3f} ms per run over {runs:g} runs; "
          f"{'+'.join(parts)} {busy * 1e3 / runs:.3f} ms ({100.0 * busy / trunk:.1f}%)",
          file=sys.stderr, flush=True)
    return busy * 1e3 / runs


def grouped_matmul_s(sc) -> float:
    """Summed device seconds of the trunk's grouped-matmul instructions in
    the window (averaged over devices)."""
    tot = 0
    for ops in sc.ops.values():
        tot += sum(min(e, sc.r.w1) - max(s, sc.r.w0) for _, s, e, mod, key, _ in ops
                   if mod is not None and key[0].startswith(GROUPED_MATMUL)
                   and part_of(mod, key) == "experts" and e > sc.r.w0 and s < sc.r.w1)
    return tot / max(len(sc.ops), 1) / 1e9
