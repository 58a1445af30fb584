"""Device ops put down to the program's named scopes, and its host spans.

The program names each phase of its superstep with a ``jax.named_scope``
``pique/<phase>`` and its host work with ``pique.<name>`` annotations
(``repro.core.tracing``).  A scope reaches each compiled instruction's
``op_name`` metadata, not the name of the device op in the trace, which is
the instruction itself (``%fusion.147 = f32[...] fusion(...)``).  So an op is
put down to a scope through the compiled HLO text of the programs the session
ran (``EpochProgram.compiled_hlo``): its instruction name, result shape
(layouts aside) and opcode are looked up among the instructions that run as
device ops, those outside fused computations.  An op belongs to the FIRST
``pique/`` scope of its ``op_name``.

Ops inside the benchmark's ``run`` spans are looked up in the superstep
programs, all others in the refresh programs; an op found in neither counts
under no scope.  Everything here works on the plain tuples of
``bench/trace.py``, so tests feed synthetic traces and HLO text.
"""

from __future__ import annotations

import bisect
import functools
import re
import sys
from collections import Counter, defaultdict

from bench import trace as trace_lib

SCOPE_PREFIX = "pique/"
SPAN_PREFIX = "pique."

_HEAD = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = (.+?) ([\w\-]+)\(")
_COMP = re.compile(r"(ENTRY )?%([\w.\-]+) ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(re.escape(SCOPE_PREFIX) + r"([A-Za-z_]\w*)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_CALLS = re.compile(r"\b(body|condition|to_apply|true_computation|false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def op_key(text: str):
    """``%name = shape opcode(...`` -> (name, shape without layouts, opcode),
    or None where the text is no HLO instruction."""
    m = _HEAD.match(text)
    if m is None:
        return None
    return m.group(1), _LAYOUT.sub("", m.group(2)), m.group(3)


def first_scope(op_name: str):
    m = _SCOPE.search(op_name)
    return m.group(1) if m else None


class Module:
    """One compiled program: its device-op instructions by key, with their
    scope path, the computation holding each, and which computations are
    the entry and the branches of a conditional."""

    def __init__(self, kind: str, text: str):
        self.kind = kind
        self.instrs: dict = {}  # key -> (op_name, computation)
        self.entry = None
        comps: dict = {}  # computation -> [(key, op_name, line)]
        cur = None
        for line in text.splitlines():
            if line and not line[0].isspace() and line.rstrip().endswith("{"):
                m = _COMP.match(line)
                cur = m.group(2) if m else None
                if m and m.group(1):
                    self.entry = cur
                if cur is not None:
                    comps[cur] = []
                continue
            if cur is None:
                continue
            key = op_key(line)
            if key is not None:
                m = _OP_NAME.search(line)
                comps[cur].append((key, m.group(1) if m else "", line))
        # computations that run as device ops: reachable from the entry
        # through while, conditional and call (fusions and comparators are not)
        self.branches: set = set()
        control, todo = set(), [self.entry] if self.entry in comps else []
        while todo:
            c = todo.pop()
            if c in control:
                continue
            control.add(c)
            for key, _, line in comps[c]:
                callee = [n for kind, n in _CALLS.findall(line)
                          if kind != "to_apply" or key[2] == "call"]
                for m in _BRANCHES.finditer(line):
                    names = [n.strip().lstrip("%") for n in m.group(1).split(",")]
                    self.branches.update(names)
                    callee += names
                todo.extend(n for n in callee if n in comps)
        for c in control:
            for key, op_name, _ in comps[c]:
                self.instrs[key] = (op_name, c)

    def lookup(self, name: str):
        """Trace event name -> the instruction's key, or None."""
        key = op_key(name)
        return key if key in self.instrs else None


class Scoped:
    """The ops of a reduced trace (``bench/trace.Reduced``) put down to the
    scopes of the session's compiled programs, ``[(kind, hlo_text)]``."""

    def __init__(self, reduced, programs):
        self.r = reduced
        self.modules = [Module(kind, text) for kind, text in programs]
        groups = {
            True: [m for m in self.modules if m.kind == "superstep"],
            False: [m for m in self.modules if m.kind != "superstep"],
        }
        runs = trace_lib.union([(s, e) for n, s, e in reduced.spans if n == "run"])
        starts = [s for s, _ in runs]

        def in_run(t):
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and t < runs[i][1]

        @functools.lru_cache(maxsize=None)
        def find(name, superstep):
            for mod in groups[superstep]:
                key = mod.lookup(name)
                if key is not None:
                    return mod, key, first_scope(mod.instrs[key][0])
            return None, None, None

        # per device: [(name, start, end, module, key, scope)]
        self.ops = {
            dev: [(n, s, e) + find(n, in_run((s + e) // 2)) for n, s, e in ops]
            for dev, ops in reduced.ops.items()
        }

    @property
    def any_scoped(self) -> bool:
        return any(o[5] for ops in self.ops.values() for o in ops)

    def scope_busy_s(self, *scopes) -> float:
        """Device-busy seconds of the ops in ``scopes``: the union of their
        intervals clipped to the window (nested container ops count once),
        averaged over devices."""
        if not self.ops:
            return 0.0
        tot = 0
        for ops in self.ops.values():
            iv = [(s, e) for _, s, e, _, _, sc in ops if sc in scopes]
            tot += trace_lib.length(trace_lib.union(trace_lib.clip(iv, self.r.w0, self.r.w1)))
        return tot / len(self.ops) / 1e9

    def _runs(self, select) -> float:
        """Executions of the computations ``select(module)`` names, in the
        window: per module, the most common count of events over their
        instructions (each runs once per execution), averaged over devices."""
        if not self.ops:
            return 0.0
        tot = 0
        for ops in self.ops.values():
            counts = Counter((id(m), k) for _, s, e, m, k, _ in ops
                             if m is not None and self.r.w0 <= (s + e) // 2 < self.r.w1)
            for mod in self.modules:
                comps = select(mod)
                per = Counter(n for (mid, k), n in counts.items()
                              if mid == id(mod) and mod.instrs[k][1] in comps)
                if per:
                    tot += max(per.items(), key=lambda kv: (kv[1], kv[0]))[0]
        return tot / len(self.ops)

    def program_runs(self, kind: str) -> float:
        """Executions of the programs of ``kind`` ("refresh") in the window."""
        return self._runs(lambda m: {m.entry} if m.kind == kind else set())

    def branch_runs(self, scope: str) -> float:
        """Executions of the conditional branches that hold ``scope``'s ops
        (the trunk runs only on epochs whose plan bought a model lane)."""
        tag = SCOPE_PREFIX + scope
        return self._runs(lambda m: {c for op_name, c in m.instrs.values()
                                     if c in m.branches and tag in op_name})

    def top_ops(self, k: int = 10) -> list:
        """``bench/trace.Reduced.top_ops`` with each op prefixed by its
        scope: ``score | %fusion.147 ...``."""
        totals: dict = defaultdict(int)
        w0, w1 = self.r.w0, self.r.w1
        for ops in self.ops.values():
            for n, s, e, _, _, sc in ops:
                if e > w0 and s < w1:
                    totals[f"{sc} | {n}" if sc else n] += min(e, w1) - max(s, w0)
        n_dev = max(len(self.ops), 1)
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[n[: trace_lib.NAME_CHARS], t / n_dev / 1e9] for n, t in top]


def gap_labels(reduced, program_spans, k: int = 10) -> list:
    """``bench/trace.Reduced.idle_gaps`` with each gap named down to the
    innermost program span covering its midpoint: ``ingest/pique.drain``;
    the benchmark's span alone where none does."""
    if not reduced.busy:
        return []
    busy = next(iter(reduced.busy.values()))
    edges = [reduced.w0] + [x for iv in busy for x in iv] + [reduced.w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        mid = (s + e) // 2
        cover = [n for n, a, b in reduced.spans if a <= mid < b]
        label = cover[-1] if cover else "host"
        inner = [(b - a, n) for n, a, b, _ in program_spans if a <= mid < b]
        if inner:
            label += "/" + min(inner)[1]
        out.append([label, (e - s) / 1e9])
    return out


def read_program_spans(path: str) -> list:
    """The program's host spans of an ``.xplane.pb``:
    ``[(name, start_ns, end_ns, {arg: value})]`` sorted by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def from_run(run):
    """The window of a traced benchmark run put down to scopes, or None
    where the program cannot name its compiled programs (a program without
    ``EpochProgram.compiled_hlo``) or none of its ops carries a scope.
    Computed once per run."""
    if run.reduced is None:
        return None
    if not hasattr(run, "scoped"):
        run.scoped = None
        hlo = getattr(run.bundle["session"].program, "compiled_hlo", None)
        if hlo is not None:
            try:
                scoped = Scoped(run.reduced, hlo())
            except Exception as e:  # a metric the run cannot read is left out
                print(f"[bench] no scopes: {type(e).__name__}: {e}", file=sys.stderr)
                return None
            if scoped.any_scoped:
                run.scoped = scoped
    return run.scoped


def epochs(run) -> int:
    """Epochs of the window's chunks, as ``epoch_device_ms`` counts them."""
    return sum(c[2] for c in run.window["chunks"])


def per_epoch_ms(run, *scopes):
    """Device ms of ``scopes`` per epoch of the window, or None."""
    sc = from_run(run)
    n = epochs(run)
    if sc is None or not n:
        return None
    busy = sc.scope_busy_s(*scopes)
    return busy * 1e3 / n if busy > 0 else None
