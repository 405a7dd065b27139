"""Device time by scope (``bench/scopes.py``), on synthetic inputs and on
a small trace recorded on a TPU v5e.

``data/toy_train_scoped.*`` were recorded by ``record_scoped_trace.py``:
a toy model trains eight steps with ``8bit-coap-adamw`` at T_u 16 inside
the ``bench/window`` span, every other step a scheduled refresh.
"""
import collections
import gzip
import json
from pathlib import Path

import pytest

from bench import flops, harness, scopes, trace_reduce
from bench.trace_reduce import Op

DATA = Path(__file__).parent / "data"
NAME = "toy_train_scoped"


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(model)/stack/while", ("forward", None, None)),
    ("jit(step)/transpose(jvp(model))/stack/while", ("backward", None, None)),
    ("jit(step)/jvp(model)/head/while/body/dot_general", ("head", None, None)),
    ("jit(step)/transpose(jvp(model))/head/while", ("head", None, None)),
    ("jit(step)/optimizer/project:3x4096x4096:float32/gather/concatenate",
     ("gather+scatter", "project:3x4096x4096:float32", None)),
    ("jit(step)/optimizer/project:3x4096x4096:float32/scatter/squeeze",
     ("gather+scatter", "project:3x4096x4096:float32", None)),
    ("jit(step)/optimizer/project:4096x18944:float32/refresh/cond/branch_1_fun/"
     "cond/branch_0_fun/dot_general",
     ("refresh", "project:4096x18944:float32", 1)),
    ("jit(step)/optimizer/project:3x4096x4096:float32/refresh/cond",
     ("refresh", "project:3x4096x4096:float32", None)),
    ("jit(step)/optimizer/project:3x4096x4096:float32/update/jit(q8)/pallas_call",
     ("update", "project:3x4096x4096:float32", None)),
    ("jit(step)/optimizer/dense/dense:3x4096:float32/update/mul",
     ("dense", "dense:3x4096:float32", None)),
    ("jit(step)/optimizer/dense/dense:3x4096:float32/gather/concatenate",
     ("gather+scatter", "dense:3x4096:float32", None)),
    ("jit(step)/optimizer/mul", ("optimizer_other", None, None)),
    ("jit(step)/step_metrics/reduce_sum", ("step_metrics", None, None)),
    ("jit(step)/add", ("unscoped", None, None)),
    ("", ("unscoped", None, None)),
])
def test_classify(op_name, want):
    assert scopes.classify(op_name) == want


HLO = """HloModule jit_step, entry_computation_layout={(f32[])->f32[]}

%body.1 (p: f32[]) -> f32[] {
  %p = f32[] parameter(0)
  %copy.1 = f32[] copy(%p)
  ROOT %fusion.2 = f32[] fusion(%copy.1), kind=kLoop, calls=%fc.1, metadata={op_name="checkpoint/rematted_computation/add"}
}

%branch.1 (p: f32[]) -> f32[] {
  ROOT %fusion.3 = f32[] fusion(%p), calls=%fc.2, metadata={op_name="jit(step)/optimizer/project:8x4:float32/refresh/cond/branch_1_fun/mul"}
}

%fc.3 (a: f32[], b: f32[]) -> (f32[], f32[]) {
  %add.1 = f32[] add(%a, %b), metadata={op_name="jit(step)/optimizer/add"}
  %abs.1 = f32[] abs(%a), metadata={op_name="jit(step)/step_metrics/abs"}
  ROOT %tuple.1 = (f32[], f32[]) tuple(%add.1, %abs.1)
}

ENTRY %main.9 (a: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %copy.7 = f32[] copy(%a)
  %while.4 = f32[] while(%copy.7), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/transpose(jvp(model))/stack/while"}
  %conditional.5 = f32[] conditional(%s, %a, %a), branch_computations={%branch.0, %branch.1}, metadata={op_name="jit(step)/optimizer/project:8x4:float32/refresh/cond"}
  %fusion.8 = (f32[], f32[]) fusion(%a, %while.4), kind=kLoop, calls=%fc.3, metadata={op_name="jit(step)/step_metrics/abs"}
  ROOT %copy.6 = f32[] copy(%while.4)
}
"""


def test_parse_hlo_inherits_a_scope():
    hlo = scopes.parse_hlo(HLO)
    assert hlo.entry == ["a", "copy.7", "while.4", "conditional.5", "fusion.8", "copy.6"]
    back = "jit(step)/transpose(jvp(model))/stack/while"
    # from the loop that calls it: an XLA copy, a rematerialized op
    assert hlo.scope["copy.1"] == hlo.scope["fusion.2"] == back
    # from the op it feeds, else from the op it reads
    assert hlo.scope["copy.7"] == hlo.scope["copy.6"] == back
    assert hlo.branch["fusion.3"] == ("conditional.5", 1)
    assert scopes.classify(hlo.scope["conditional.5"])[2] is None
    assert hlo.mixed == {"fusion.8": "optimizer_other+step_metrics"}


def _ops(*spans):
    return [Op(name, start, dur) for name, start, dur in spans]


def test_self_time_subtracts_nested_ops():
    ops = _ops(("while", 0, 100), ("a", 10, 20), ("b", 40, 30), ("c", 45, 5),
               ("d", 120, 10))
    got = scopes.self_times(ops)
    assert [s for s, _ in got] == [50, 20, 25, 5, 10]
    assert [t for _, t in got] == [True, False, False, False, True]
    assert sum(s for s, _ in got) == 110  # the union of the intervals


def test_steps_open_at_the_entrys_first_scheduled_op():
    # copy.7 is the entry's first instruction the trace holds ("a" is a
    # parameter): each run of it opens a step, whatever runs first
    hlo = scopes.parse_hlo(HLO)
    step = [("fusion.8", 0, 5), ("copy.7", 10, 5), ("while.4", 20, 50),
            ("fusion.2", 30, 10), ("conditional.5", 80, 10)]
    ops = _ops(*[(n, s + k * 100, d) for k in range(3) for n, s, d in step])
    scoped = scopes.attribute(ops, hlo, 3)
    # a step is 65 ns of self time (the while's 50 hold fusion.2's 10);
    # fusion.8 runs before the next step's copy.7, the first one before any
    assert scoped.steps == 3 and scoped.busy_ns == [70, 70, 65]
    assert scoped.part_ns[1] == {"backward": 55, "refresh": 10, "step_metrics": 5}
    assert scopes.attribute(ops, hlo, 2) is None  # three steps ran, not two


def _scoped(refreshed, refresh_ns=None):
    n = len(refreshed)
    ns = refresh_ns or [10 if r else 1 for r in refreshed]
    parts = [collections.Counter(refresh=t) for t in ns]
    return scopes.Scoped(n, [0] * n, parts, [set(r) for r in refreshed], [], {}, {})


OPT = {"rank": 2, "min_dim": 2, "t_update": 8, "phases": {"a": 0, "b": 4, "c": 6}}
SHAPES = {"a": (4, 8), "b": (2, 4, 8), "c": (2, 4, 8)}


def test_scheduled_steps_place_the_window_on_the_schedule():
    # counts 2..7: "c" (phase 6) at count 2, "b" (phase 4) at 4, "a" at 0/8
    a, bc = "project:4x8:float32", "project:2x4x8:float32"
    got = scopes.scheduled_steps(
        _scoped([{(bc, 2)}, set(), {(bc, 1)}, set(), set(), set()]), OPT, SHAPES)
    assert got == [0, 2]
    got = scopes.scheduled_steps(
        _scoped([set(), set(), set(), {(a, 1)}, set(), {(bc, 2)}]), OPT, SHAPES)
    assert got == [3, 5]


def test_refresh_cycle_reads_the_same_wherever_the_window_falls():
    # "a" is one 4 x 8 matrix, "b" and "c" two each: work 1 : 2 : 2. With
    # time in proportion to work, every window reads the whole cycle's time.
    a, bc = "project:4x8:float32", "project:2x4x8:float32"
    branch = {"a": (a, 1), "b": (bc, 1), "c": (bc, 2)}
    unit = {"a": 3, "b": 6, "c": 6}  # ns
    for c0 in range(8):
        due = [[p for p, ph in OPT["phases"].items() if (c0 + k + ph) % 8 == 0]
               for k in range(5)]
        if not any(due):
            continue
        # the switches alone take 1 ns a step
        scoped = _scoped([{branch[p] for p in d} for d in due],
                         [1 + sum(unit[p] for p in d) for d in due])
        assert scopes.placement(scoped, OPT, SHAPES) == c0
        assert scopes.refresh_cycle_ms(scoped, OPT, SHAPES) == pytest.approx(15e-6)


@pytest.mark.parametrize("refreshed", [
    [set(), set()],  # nothing ran: no placement
    [{("project:2x4x8:float32", 2)}, {("project:2x4x8:float32", 1)}],  # disagree
    [{("project:2x4x8:float32", 2)}, set(), set(), set(), set(), set(), set(), set(),
     {("project:4x8:float32", 1)}],  # count 2 then count 10: "a" is not due
    [{("project:2x4x8:float32", 3)}],  # no third group
])
def test_scheduled_steps_refuse_what_the_schedule_does_not_fit(refreshed):
    assert scopes.scheduled_steps(_scoped(refreshed), OPT, SHAPES) is None


# ------------------------------------------------------ the recorded trace
@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("scoped") / (NAME + ".xplane.pb")
    path.write_bytes(gzip.decompress((DATA / (NAME + ".xplane.pb.gz")).read_bytes()))
    reduced = trace_reduce.reduce_file(str(path))
    text = gzip.decompress((DATA / (NAME + ".hlo.txt.gz")).read_bytes()).decode()
    meta = json.loads((DATA / (NAME + ".json")).read_text())
    return reduced, text, meta


@pytest.fixture()
def run(recorded, monkeypatch):
    reduced, text, meta = recorded
    monkeypatch.setattr(scopes, "step_hlo", lambda run: text)
    cell = harness.Cell(name="toy", chips=1, config={},
                        traffic={"optimizer": meta["optimizer"]}, limits={},
                        end_to_end=[], per_layer=[], reference=None)
    out = harness.Run(cell=cell, peaks={}, trace=reduced,
                      traced_steps=meta["traced_steps"],
                      shapes={p: tuple(s) for p, s in meta["shapes"].items()})
    return out


def test_every_window_op_is_an_instruction(recorded):
    reduced, text, _ = recorded
    hlo = scopes.parse_hlo(text)
    assert reduced.chips == 1
    assert {op.name for op in reduced.ops} <= set(hlo.scope)


def test_self_times_sum_to_busy(recorded):
    reduced, text, meta = recorded
    scoped = scopes.attribute(reduced.ops, scopes.parse_hlo(text), meta["traced_steps"])
    assert scoped is not None and scoped.steps == meta["traced_steps"]
    assert sum(scoped.busy_ns) == pytest.approx(reduced.busy_ns, rel=1e-3)
    total = sum(sum(d.values()) for d in scoped.part_ns)
    assert total == sum(scoped.busy_ns)
    # every part of a training step shows up, and little is left unscoped
    got = {p for d in scoped.part_ns for p, v in d.items() if v > 0}
    assert {"forward", "backward", "head", "gather+scatter", "refresh", "update",
            "dense", "step_metrics"} <= got
    assert sum(d["unscoped"] for d in scoped.part_ns) < 0.01 * total
    # CEU's sum fuses with apply_updates: the time is charged to one part
    assert scoped.mixed_ns.get("optimizer_other+step_metrics", 0) > 0
    top = scoped.summary()["top_self_ms"]
    assert len(top) == 10 and [t for *_, t in top] == sorted((t for *_, t in top),
                                                              reverse=True)
    assert all(part in scopes.PARTS for _, part, _ in top)


def test_the_loop_that_ran_is_the_harness_frames():
    from bench.train_cell import TrainCell

    run = harness.Run(cell=None, peaks={})
    other = harness.Run(cell=None, peaks={})

    def run_cell(run):  # as the harness holds them when it calls a reader
        cell = harness.Cell("toy", 1, {}, {"optimizer": {}}, {}, [], [], None)
        runner = TrainCell(cell, {}, 0)
        runner.loop = object()
        return runner, scopes.cell_that_ran(run), scopes.cell_that_ran(other)

    runner, found, other_found = run_cell(run)
    assert found is runner and other_found is runner  # both in run_cell's frame
    # a run no frame holds beside a TrainCell with a loop (outside the
    # assert, whose rewriting would keep the run in this frame)
    lone = scopes.cell_that_ran(harness.Run(cell=None, peaks={}))
    assert lone is None


def test_step_hlo_is_the_executable_the_loop_ran(tmp_path):
    import jax

    from bench import train_cell
    from bench.tests import tiny

    cell = harness.load_cell(tiny.CELL, tiny.make_root(tmp_path, "8bit-coap-adamw"))
    runner = train_cell.TrainCell(cell, harness.arch_fields(cell.config), 2 ** 31 + 3)
    runner.setup(warmup=False)
    run = harness.Run(cell=cell, peaks={})
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name)
        if name.endswith("backend_compile_duration") else None)
    text = scopes.step_hlo(run)
    assert compiles == []
    hlo = scopes.parse_hlo(text)
    parts = {scopes.classify(n)[0] for n in hlo.scope.values()}
    assert {"forward", "backward", "update", "refresh", "gather+scatter"} <= parts


def test_readers_read_the_recording(run):
    values = {name: harness.metric_reader(name)(run)
              for name in ("opt_update_ms", "bucket_copy_ms", "refresh_ms")}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["bucket_copy_ms"] < values["opt_update_ms"]


def test_refresh_reads_only_the_scheduled_steps(run, recorded):
    _, _, meta = recorded
    scoped = scopes.for_run(run)
    opt = meta["optimizer"]
    first, t_u = meta["first_step"], opt["t_update"]
    want = [k for k in range(meta["traced_steps"])
            if any((first + k + p) % t_u == 0 for p in opt["phases"].values())]
    assert 0 < len(want) < meta["traced_steps"]
    assert scopes.scheduled_steps(scoped, opt, run.shapes) == want
    per_step = [d["refresh"] * 1e-6 for d in scoped.part_ns]
    # the scheduled steps' time less the other steps' mean, scaled by m n r
    # from the matrices they refreshed to all eight (every rank is 64)
    work = {p: c * m * n for p, c, m, n, _ in flops.projected_matrices(run.shapes, opt)}
    seen = sum(work[p] for k in want for p, ph in opt["phases"].items()
               if (first + k + ph) % t_u == 0)
    off = [t for k, t in enumerate(per_step) if k not in want]
    base = sum(off) / len(off)
    cycle = sum(per_step[k] - base for k in want) * sum(work.values()) / seen
    assert harness.metric_reader("refresh_ms")(run) == pytest.approx(cycle)
    # off the schedule only the switches and their no-op branches run
    assert max(off) < min(per_step[k] for k in want)
