"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
operations are the events of the ``XLA Ops`` line of each ``/device:``
plane; host activity is every event of the ``/host:CPU`` plane. The
traced window is the host span ``bench/window`` that the harness opens
around the measured steps.

* busy: the union of the device operations' intervals inside the window,
  averaged over the chips; the idle share is ``1 - busy / window``;
* each operation's total time, by name;
* idle gaps: the stretches of the window in which no operation ran on the
  device, each labelled with the innermost host event that spans its
  middle (what the host was doing).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
GAPS = 10  # longest idle gaps kept, labelled


@dataclasses.dataclass
class Op:
    name: str  # the HLO instruction's name, e.g. "fusion.27"
    start: int  # ns
    dur: int


@dataclasses.dataclass
class Reduced:
    window: tuple  # (start_ns, end_ns)
    chips: int
    ops: list  # [Op] inside the window, every chip
    busy_ns: int  # union of op intervals, summed over chips
    gaps: list  # [(start_ns, dur_ns, host label)]: the first chip's longest

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9 / max(self.chips, 1)

    def op_totals(self) -> dict:
        out = collections.Counter()
        for op in self.ops:
            out[op.name] += op.dur
        return {k: v * 1e-9 / max(self.chips, 1) for k, v in out.items()}

    def matching(self, pattern: str) -> list:
        """Ops whose name matches ``pattern`` (a regex). A Pallas kernel's
        instruction is named after the function that calls it, e.g.
        ``vmap_jit_coap_fused_update_bp_pallas__.11``."""
        rx = re.compile(pattern)
        return [op for op in self.ops if rx.search(op.name)]

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_totals().items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[label, dur * 1e-9] for _, dur, label in self.gaps[:n]]}


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host, chips = [], []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events)
        elif plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                # An event's name is the instruction's HLO text,
                # "%fusion.27 = bf16[...] fusion(...)"; keep its name.
                ops.extend(Op(ev.name.split(" = ", 1)[0].lstrip("%"),
                              int(ev.start_ns), int(ev.duration_ns))
                           for ev in line.events)
            if ops:
                chips.append(ops)
    if not chips:
        raise ValueError(f"{path}: no device operations in the trace")
    spans = [(s, s + d) for name, s, d in host if name == WINDOW]
    if spans:
        w0, w1 = min(s for s, _ in spans), max(e for _, e in spans)
    else:
        w0 = min(op.start for ops in chips for op in ops)
        w1 = max(op.start + op.dur for ops in chips for op in ops)
    inside = []
    busy = 0
    first_union = None
    for ops in chips:
        mine = [op for op in ops if op.start < w1 and op.start + op.dur > w0]
        inside.extend(mine)
        union = _union((max(op.start, w0), min(op.start + op.dur, w1)) for op in mine)
        busy += sum(e - s for s, e in union)
        if first_union is None:
            first_union = union
    gaps = []
    prev = w0
    for s, e in first_union + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s - prev))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    labelled = [(s, d, _host_label(host, s + d // 2)) for s, d in gaps[:GAPS]]
    return Reduced(window=(w0, w1), chips=len(chips), ops=inside, busy_ns=busy,
                   gaps=labelled)


def _host_label(host, t: int) -> str:
    best = None
    for name, s, d in host:
        if name != WINDOW and s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "no host event"


def reduce_dir(log_dir: str) -> Reduced:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise ValueError(f"{log_dir}: expected one .xplane.pb, found {paths}")
    return reduce_file(paths[0])
