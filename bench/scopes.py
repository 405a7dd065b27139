"""Device time by the program's scopes, from a traced window.

Each operation of the trace (``bench/trace_reduce.py``) is named by its
HLO instruction. The optimized HLO text of the executable the window ran
(``TrainLoop.step_hlo_text`` on the harness's loop, after the window)
gives each instruction's ``op_name``: the ``jax.named_scope`` path the
program put on it (``train/step.py``, ``models/model.py``,
``core/coap_adam.py``). An instruction with no scope of its own (an op of
a rematerialized body, an XLA copy) takes the scope of the instruction
that calls its computation (a loop, a branch), else of the nearest one it
feeds, else of the nearest one it reads.

* self time: an operation's duration less the union of the operations
  nested inside it (a ``while`` holds its body's operations);
* steps: the entry computation's first instruction in schedule order
  that the trace holds opens a step each time it runs;
* parts: ``forward`` and ``backward`` (``model`` and
  ``transpose(jvp(model))``, the loss head aside), ``head``, the
  optimizer's ``gather+scatter`` (every bucket's), ``refresh`` and
  ``update`` (projected buckets'), ``dense`` (dense Adam buckets') and
  ``optimizer_other`` (clipping, the learning rate, ``apply_updates``),
  ``step_metrics``, and ``unscoped``;
* refreshes: branch ``k >= 1`` of a bucket's refresh switch (the
  conditional scoped ``.../<bucket>/refresh/cond``) is its ``k``-th phase
  group, so the steps in which one ran place the traced steps on the
  traffic's schedule (``phases``, ``t_update``); the refresh time of the
  matrices the window refreshed, scaled by work to every projected
  matrix, is the refresh time of one ``t_update`` cycle.

One chip only: the reduced trace does not say which chip ran an
operation.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import re
import sys
import time
from typing import Optional

OPTIMIZER = ("gather+scatter", "refresh", "update", "dense", "optimizer_other")
PARTS = ("forward", "backward", "head") + OPTIMIZER + ("step_metrics", "unscoped")
TOPS = ("optimizer", "step_metrics", "model", "jvp(model)", "transpose(jvp(model))")

_INST = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_NAMES = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(branch_computations|called_computations)=\{([^}]*)\}")
_BRANCH = re.compile(r"^branch_(\d+)_fun$")


@dataclasses.dataclass
class Hlo:
    scope: dict  # instruction name -> op_name that carries a scope, or ""
    entry: list  # instruction names of the entry computation, in schedule order
    branch: dict  # instruction name -> (conditional, k) when it sits in branch k
    mixed: dict  # fusion name -> the parts its fused instructions come from, if 2+


def _scoped(op_name: str) -> bool:
    return any(part in TOPS for part in op_name.split("/"))


def parse_hlo(text: str) -> Hlo:
    """Each instruction's scope: its own ``op_name`` if that names one;
    else that of the instruction calling its computation (a loop body, a
    branch, a fusion); else that of the nearest instruction it feeds (an
    XLA copy into a kernel's layout), else of the nearest it reads (a copy
    into the output's layout)."""
    own, comp_of, callers, branch_of, fused = {}, {}, {}, {}, {}
    users, operands = collections.defaultdict(list), collections.defaultdict(list)
    entry, comp, is_entry = [], None, False
    for line in text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                comp = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
                is_entry = line.startswith("ENTRY")
            continue
        m = _INST.match(line)
        if not m or comp is None:
            continue
        name = m.group(1).lstrip("%")
        comp_of[name] = comp
        if is_entry:
            entry.append(name)
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        called = _CALLS.findall(line)
        for kind, group in _CALL_LISTS.findall(line):
            comps = [c.strip().lstrip("%") for c in group.split(",") if c.strip()]
            called += comps
            if kind == "branch_computations":
                branch_of.update((c, (name, k)) for k, c in enumerate(comps))
        for c in called:
            callers.setdefault(c, name)
        if " fusion(" in line:
            fused[name] = called
        for operand in set(_NAMES.findall(line)) - set(called) - {name}:
            users[operand].append(name)
            operands[name].append(operand)
    scope = {}

    def by_caller(name, depth=0):
        if name not in scope:
            caller = callers.get(comp_of[name])
            scope[name] = (own[name] if _scoped(own[name]) else
                           by_caller(caller, depth + 1) if caller and depth < 64 else "")
        return scope[name]

    for name in own:
        by_caller(name)
    for name in [n for n in own if not scope[n]]:
        for graph in (users, operands):
            frontier = graph[name]
            for _ in range(4):
                scope[name] = next((scope[u] for u in frontier if scope.get(u)), "")
                if scope[name]:
                    break
                frontier = [v for u in frontier for v in graph[u]]
            if scope[name]:
                break
    branch = {n: branch_of[comp_of[n]] for n in own if comp_of[n] in branch_of}
    parts_in = collections.defaultdict(set)
    for n, op_name in own.items():
        if _scoped(op_name):
            parts_in[comp_of[n]].add(classify(op_name)[0])
    mixed = {}
    for n, comps in fused.items():
        parts = set().union(*(parts_in[c] for c in comps))
        if len(parts) > 1:
            mixed[n] = "+".join(sorted(parts))
    return Hlo(scope=scope, entry=entry, branch=branch, mixed=mixed)


def classify(op_name: str):
    """(part, bucket, branch) of a scope path: ``bucket`` is the
    optimizer bucket's label, ``branch`` the ``k`` of the first
    ``branch_<k>_fun`` under its ``refresh`` scope (None in the refresh
    switch itself)."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p == "step_metrics":
            return "step_metrics", None, None
        if p == "optimizer":
            rest = parts[i + 1:]
            if rest and rest[0] == "dense" and len(rest) > 1:
                sub = rest[2] if len(rest) > 2 else ""
                part = "gather+scatter" if sub in ("gather", "scatter") else "dense"
                return part, rest[1], None
            if rest and ":" in rest[0]:
                sub = rest[1] if len(rest) > 1 else ""
                if sub in ("gather", "scatter"):
                    return "gather+scatter", rest[0], None
                if sub == "refresh":
                    branch = next((int(m.group(1)) for m in map(_BRANCH.match, rest[2:])
                                   if m), None)
                    return "refresh", rest[0], branch
                if sub == "update":
                    return "update", rest[0], None
            return "optimizer_other", None, None
        if p in ("model", "jvp(model)", "transpose(jvp(model))"):
            if "head" in parts[i + 1:]:
                return "head", None, None
            return ("backward" if p.startswith("transpose(") else "forward"), None, None
    return "unscoped", None, None


def self_times(ops) -> list:
    """Each op's duration less the union of the ops directly nested in it
    (one chip's ops). Returns (self_ns, top_level) per op, in input order."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].dur))
    children = collections.defaultdict(list)
    top = [False] * len(ops)
    stack = []
    for i in order:
        s, e = ops[i].start, ops[i].start + ops[i].dur
        while stack and not (ops[stack[-1]].start <= s
                             and e <= ops[stack[-1]].start + ops[stack[-1]].dur):
            stack.pop()
        if stack:
            children[stack[-1]].append((s, e))
        else:
            top[i] = True
        stack.append(i)
    out = []
    for i, op in enumerate(ops):
        covered, end = 0, None
        for s, e in sorted(children[i]):
            if end is None or s > end:
                covered += e - s
                end = e
            elif e > end:
                covered += e - end
                end = e
        out.append((op.dur - covered, top[i]))
    return out


@dataclasses.dataclass
class Scoped:
    steps: int
    busy_ns: list  # per step: summed self time of its ops
    part_ns: list  # per step: {part: self ns}
    refreshed: list  # per step: {(bucket, branch)} of refresh branches that ran
    unmapped: list  # window op names the HLO does not hold
    mixed_ns: dict  # "part+part" -> self ns of fusions whose instructions span both
    op_ns: dict  # instruction name -> (part, summed self ns)

    def per_step_ms(self, *parts) -> float:
        return 1e-6 * sum(d.get(p, 0) for d in self.part_ns for p in parts) / self.steps

    def summary(self) -> dict:
        return {"steps": self.steps,
                "busy_ms": 1e-6 * sum(self.busy_ns) / self.steps,
                "self_ms": {p: self.per_step_ms(p) for p in PARTS},
                "fused_across_ms": {k: 1e-6 * v / self.steps
                                    for k, v in sorted(self.mixed_ns.items())},
                "top_self_ms": [[name, part, 1e-6 * ns / self.steps] for name, (part, ns)
                                in sorted(self.op_ns.items(), key=lambda kv: -kv[1][1])[:10]]}


def attribute(ops, hlo: Hlo, steps: int) -> Optional[Scoped]:
    """Self time by part and step for one chip's window ops, or None when
    the ops do not fall into ``steps`` steps of the entry computation."""
    timed = self_times(ops)
    names = {op.name for op in ops}
    first = next((name for name in hlo.entry if name in names), None)
    opens = sorted(op.start for op in ops if op.name == first)
    if first is None or steps < 1 or len(opens) != steps:
        return None
    busy = [0] * steps
    parts = [collections.Counter() for _ in range(steps)]
    refreshed = [set() for _ in range(steps)]
    kinds = {}  # instruction name -> classify() of its scope
    mixed, op_ns = collections.Counter(), collections.Counter()
    for op, (self_ns, _) in zip(ops, timed):
        k = bisect.bisect_right(opens, op.start) - 1
        if k < 0:
            continue
        if op.name not in kinds:
            kinds[op.name] = classify(hlo.scope.get(op.name, ""))
        part = kinds[op.name][0]
        busy[k] += self_ns
        parts[k][part] += self_ns
        op_ns[op.name] += self_ns
        if op.name in hlo.mixed:
            mixed[hlo.mixed[op.name]] += self_ns
        # An op directly in branch j >= 1 of a bucket's refresh switch (or
        # cond) says that group refreshed: computations nested deeper can be
        # shared between branches.
        cond, j = hlo.branch.get(op.name, (None, 0))
        if j and cond not in kinds:
            kinds[cond] = classify(hlo.scope.get(cond, ""))
        if j and kinds[cond][0] == "refresh" and kinds[cond][2] is None:
            refreshed[k].add((kinds[cond][1], j))
    unmapped = sorted({op.name for op in ops} - set(hlo.scope))
    return Scoped(steps, busy, parts, refreshed, unmapped, dict(mixed),
                  {n: (kinds[n][0], ns) for n, ns in op_ns.items()})


def _dims(bucket: str) -> str:
    """``project:3x4096x13696:float32`` -> ``3x4096x13696``."""
    return bucket.split(":")[1] if bucket.count(":") == 2 else bucket


def placement(scoped: Scoped, opt: dict, shapes: dict) -> Optional[int]:
    """The first traced step's optimizer count modulo ``t_update``, from
    the refresh branches that ran; None where no placement on the
    schedule fits.

    A leaf refreshes at optimizer count ``c`` when ``(c + phase) % t_update
    == 0``. A bucket's branch ``k`` is its ``k``-th phase group (phases,
    ascending, of the matrices of its shape), so a step in which it ran
    has ``c = -phase (mod t_update)``; every such step must agree on the
    first traced step's count, and the steps the schedule then names must
    be those in which a refresh ran."""
    t_u = int(opt["t_update"])
    phases_of = collections.defaultdict(set)
    for path, phase in opt["phases"].items():
        phases_of["x".join(str(d) for d in shapes[path])].add(int(phase))
    groups = {dims: sorted(ph) for dims, ph in phases_of.items()}
    starts = set()
    for k, ran in enumerate(scoped.refreshed):
        for bucket, branch in ran:
            g = groups.get(_dims(bucket), [])
            if branch > len(g):
                return None
            starts.add((-g[branch - 1] - k) % t_u)
    if len(starts) != 1:
        return None
    c0 = starts.pop()
    if _due(c0, scoped.steps, opt) != [k for k, ran in enumerate(scoped.refreshed) if ran]:
        return None
    return c0


def _due(c0: int, steps: int, opt: dict, paths=None) -> list:
    """The steps among ``steps`` from count ``c0`` at which one of
    ``paths`` (every phased path by default) refreshes."""
    t_u = int(opt["t_update"])
    phases = [int(p) for q, p in opt["phases"].items() if paths is None or q in paths]
    return [k for k in range(steps) if any((c0 + k + p) % t_u == 0 for p in phases)]


def scheduled_steps(scoped: Scoped, opt: dict, shapes: dict) -> Optional[list]:
    """The traced steps that the traffic's schedule refreshes, as indices
    of ``scoped``'s steps; None where no placement fits (``placement``)."""
    c0 = placement(scoped, opt, shapes)
    return None if c0 is None else _due(c0, scoped.steps, opt)


def refresh_cycle_ms(scoped: Scoped, opt: dict, shapes: dict) -> Optional[float]:
    """Device time of one refresh of every projected matrix, in ms: the
    self time under the ``refresh`` scopes in the traced steps that the
    schedule refreshes, less that of a step that refreshes nothing (the
    switches alone, their mean over the other traced steps), times the
    work of every projected matrix over that of the matrices those steps
    refreshed. A matrix's work is ``m n r`` (``flops.projected_matrices``),
    the order of Eqn 6's products, so the reading does not depend on which
    buckets fall in the window. None where no placement fits, every traced
    step refreshes, or a phased path is not a projected matrix."""
    from bench import flops

    c0 = placement(scoped, opt, shapes)
    if c0 is None:
        return None
    work = {path: count * m * n * r
            for path, count, m, n, r in flops.projected_matrices(shapes, opt)}
    due = _due(c0, scoped.steps, opt)
    idle = [d["refresh"] for k, d in enumerate(scoped.part_ns) if k not in due]
    if set(opt["phases"]) - set(work) or not idle:
        return None
    seen = sum(work[path] * len(_due(c0, scoped.steps, opt, {path}))
               for path in opt["phases"])
    ns = sum(scoped.part_ns[k]["refresh"] for k in due) - len(due) * sum(idle) / len(idle)
    return 1e-6 * ns * sum(work[p] for p in opt["phases"]) / seen


def cell_that_ran(run):
    """The ``bench/train_cell.TrainCell`` whose window made ``run``: the
    one in the caller's frame that holds ``run`` (the harness's
    ``run_cell``, which calls the readers before it frees the loop). The
    harness hands a reader the ``Run`` alone; a field for the loop is a
    harness edit (PERF.md section 7)."""
    from bench.train_cell import TrainCell

    frame = sys._getframe(1)
    while frame is not None:
        values = list(frame.f_locals.values())
        if any(v is run for v in values):
            cell = next((v for v in values if isinstance(v, TrainCell)), None)
            if cell is not None and hasattr(cell, "loop"):
                return cell
        frame = frame.f_back
    return None


def step_hlo(run) -> Optional[str]:
    """The optimized HLO text of the executable the window ran:
    ``TrainLoop.step_hlo_text`` on the loop that ran, for its state and a
    batch of its feed (the jitted step's cache holds that executable, so
    nothing compiles). None where the program has no ``step_hlo_text`` or
    no loop is found."""
    cell = cell_that_ran(run)
    if cell is None or not hasattr(cell.loop, "step_hlo_text"):
        return None
    return cell.loop.step_hlo_text(cell.state, cell.batches[0])


def for_run(run) -> Optional[Scoped]:
    """The run's attribution, computed once and kept on the run; logs one
    ``[scopes]`` line to standard error (per step: self time by part, by
    fusions whose instructions span parts, of the ten longest ops; the
    refresh steps; Pallas launches; the idle gaps). None without a
    one-chip trace, or where the program names no scope or the HLO lacks
    a window op."""
    if "scopes" in vars(run):
        return vars(run)["scopes"]
    scoped, note = None, {}
    if run.trace is not None and run.trace.chips == 1 and run.traced_steps:
        t0 = time.perf_counter()
        text = step_hlo(run)
        note["hlo_s"] = time.perf_counter() - t0
        if text is not None:
            scoped = attribute(run.trace.ops, parse_hlo(text), run.traced_steps)
    if scoped is not None and (scoped.unmapped or not any(
            d[p] for d in scoped.part_ns for p in OPTIMIZER)):
        note["unmapped"] = scoped.unmapped[:12]
        scoped = None
    if scoped is not None:
        note.update(scoped.summary())
        opt = run.cell.traffic["optimizer"]
        note["refresh_steps"] = scheduled_steps(scoped, opt, run.shapes)
        note["refreshed"] = [sorted(r) for r in scoped.refreshed]
        note["refresh_step_ms"] = [1e-6 * d["refresh"] for d in scoped.part_ns]
    if run.trace is not None:
        kernels = collections.Counter(op.name.rsplit(".", 1)[0] for op in run.trace.ops
                                      if "pallas" in op.name)
        note["pallas_per_step"] = {k: n / max(run.traced_steps, 1)
                                   for k, n in sorted(kernels.items())}
        note["idle_gaps_ms"] = [[label, dur * 1e-6] for _, dur, label in run.trace.gaps]
    print("[scopes] " + json.dumps(note), file=sys.stderr, flush=True)
    vars(run)["scopes"] = scoped
    return scoped
