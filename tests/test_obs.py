"""Observability subsystem: span tracer, counter registry, measured-cost
calibration, fleet_status CLI, and the hardened liveness/metrics paths.

Five layers, matching the ``obs/`` contract:

  * **tracer** — nested spans round-trip through trace.jsonl with
    parent/depth recovered per thread, torn lines are skipped, disabled
    tracing is a shared no-op object before jax is imported, every span
    is a host event of a ``jax.profiler`` trace, and the Perfetto export
    is a well-formed Chrome ``trace_event`` document;
  * **registry** — thread-safe counters/gauges, snapshot tidiness, and
    cross-process merge semantics (counters sum, gauges last-writer-win);
  * **liveness/metrics hardening** — concurrent ``beat``/``touch`` never
    publish a torn heartbeat (per-writer temp names), the registry phase
    gauge rides touches, and ``MetricsLogger.log`` fetches the whole row
    with ONE ``jax.device_get``;
  * **calibration** — ``plan.solve`` is bit-identical without an
    artifact, a ``coap-calib/v1`` artifact rescales predicted seconds
    (explicit path and ``REPRO_COAP_CALIB``), the NNLS fit recovers known
    constants, and the planned refresh schedule matches the stagger
    predicates including the step-0 whole-bucket Eqn-7 init;
  * **end-to-end** — THE acceptance scenario: a traced elastic
    kill + shrink + resume run exports a Perfetto-loadable trace with
    restore/migrate/compile/step spans per attempt, fits a calibration
    artifact the solver consumes, and ``fleet_status --json`` reports the
    same run's phase/step/staleness/counters.
"""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import pytest

from repro.obs import calib as obs_calib
from repro.obs.registry import Registry, get_registry, merge_snapshots
from repro.obs.trace import (
    Tracer,
    configure,
    export_perfetto,
    get_tracer,
    read_trace,
    trace_events,
)
from repro.plan.cost import CALIB_CODEC, Calibration
from repro.plan.solver import solve
from repro.train.fault_tolerance import Heartbeat
from repro.train.metrics import MetricsLogger

_KW = dict(min_dim=8, t_update=4, lam=2, stagger_groups=2)


@pytest.fixture(autouse=True)
def _clean_obs():
    """Tracer and registry are process-wide singletons: put them back."""
    yield
    configure(None)
    get_registry().reset()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------
def test_span_nesting_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    t = configure(path, host="h0")
    assert t.enabled
    with t.span("elastic/attempt", attempt=0):
        with t.span("loop/step", step=3) as sp:
            sp.set(late="attr")
        t.instant("supervisor/kill", reason="stale")
    with pytest.raises(RuntimeError):
        with t.span("elastic/replan"):
            raise RuntimeError("boom")

    rows = read_trace(path)
    by_name = {r["name"]: r for r in rows}
    step = by_name["loop/step"]
    assert step["parent"] == "elastic/attempt" and step["depth"] == 1
    assert step["attrs"] == {"step": 3, "late": "attr"}
    attempt = by_name["elastic/attempt"]
    assert attempt["parent"] is None and attempt["depth"] == 0
    assert attempt["host"] == "h0"
    # Child is written first (exit order) but nesting comes from fields.
    assert rows.index(step) < rows.index(attempt)
    assert attempt["dur"] >= step["dur"] >= 0
    assert by_name["supervisor/kill"]["ph"] == "i"
    assert by_name["elastic/replan"]["attrs"]["error"] == "RuntimeError"


def test_disabled_tracer_is_shared_noop(tmp_path):
    # Before jax is imported a disabled span is one shared no-op object: no
    # allocation. Once jax is imported it is a profiler annotation alone,
    # and still writes nothing.
    code = (
        "import sys\n"
        "from repro.obs.trace import configure\n"
        "t = configure(None)\n"
        "assert not t.enabled\n"
        "s1, s2 = t.span('a', x=1), t.span('b')\n"
        "assert s1 is s2\n"
        "with s1 as sp:\n"
        "    sp.set(y=2)\n"
        "t.instant('c')\n"
        "assert 'jax' not in sys.modules\n"
    )
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(sys.path))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=tmp_path)
    t = configure(None)
    assert not t.enabled
    with t.span("a", x=1) as sp:
        sp.set(y=2)
    t.instant("c")  # no-op, no file
    assert list(tmp_path.iterdir()) == []


@pytest.mark.parametrize("jsonl", [False, True])
def test_span_is_a_profiler_host_event(tmp_path, jsonl):
    """Every span lands in a jax.profiler trace as a host event of its
    name, on the profiler's clock, with or without a trace.jsonl."""
    path = str(tmp_path / "trace.jsonl") if jsonl else None
    t = configure(path)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    with t.span("loop/step", step=1):
        with t.span("loop/dispatch"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
    events = {}
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, ev)
    assert {"loop/step", "loop/dispatch"} <= set(events)
    outer, inner = events["loop/step"], events["loop/dispatch"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    assert inner.duration_ns >= 2e6
    rows = read_trace(path) if jsonl else []
    assert [r["name"] for r in rows] == (["loop/dispatch", "loop/step"]
                                         if jsonl else [])


def test_configure_same_path_appends(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t1 = configure(path, host="h0")
    with t1.span("a"):
        pass
    t2 = configure(path, host="h0")  # worker re-boot, same journal
    assert t2 is t1
    with t2.span("b"):
        pass
    assert {r["name"] for r in read_trace(path)} == {"a", "b"}


def test_read_trace_skips_torn_lines(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = configure(path, host="h")
    with t.span("good", k=1):
        pass
    with open(path, "a") as f:
        f.write('{"name": "torn", "ts": 1.0, "dur":')  # killed mid-append
    rows = read_trace(path)
    assert [r["name"] for r in rows] == ["good"]
    assert read_trace(str(tmp_path / "absent.jsonl")) == []


def test_perfetto_export_schema(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = configure(path, host="h0")
    with t.span("loop/step", step=1):
        time.sleep(0.002)
    t.instant("supervisor/drain")
    out = str(tmp_path / "perfetto.json")
    doc = export_perfetto(path, out)
    assert json.load(open(out)) == doc
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "loop/step" and x["cat"] == "loop"
    assert x["dur"] >= 2000  # µs
    assert x["args"] == {"step": 1}
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t"
    # Every event has the keys chrome://tracing requires.
    for e in evs:
        assert {"ph", "name", "pid", "tid"} <= set(e)


def test_tracer_thread_safety(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = configure(path, host="h")

    def work(i):
        for j in range(20):
            with t.span(f"thread/{i}", j=j):
                pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    rows = read_trace(path)
    assert len(rows) == 80  # no torn/interleaved lines
    # Per-thread nesting: every span saw an empty stack (depth 0).
    assert all(r["depth"] == 0 for r in rows)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_registry_counters_gauges_snapshot():
    r = Registry()
    r.inc("a/b")
    r.inc("a/b", 2)
    r.inc("frac", 0.5)
    r.set_phase("restore")
    r.set_gauge("g", 7)
    assert r.get("a/b") == 3.0
    assert r.get("absent") == 0.0
    assert r.gauge("phase") == "restore"
    assert r.gauge("absent", "dflt") == "dflt"
    snap = r.snapshot()
    assert snap["counters"] == {"a/b": 3, "frac": 0.5}  # int when integral
    assert isinstance(snap["counters"]["a/b"], int)
    assert snap["gauges"] == {"phase": "restore", "g": 7}
    # Snapshots are stamped for deterministic gauge merging.
    assert isinstance(snap["ts"], float) and snap["ts"] > 0
    assert snap["host"] == os.environ.get("REPRO_HOST_ID", "")
    # Snapshot is a copy, not a view.
    snap["counters"]["a/b"] = 99
    assert r.get("a/b") == 3.0
    r.reset()
    empty = r.snapshot()
    assert empty["counters"] == {} and empty["gauges"] == {}


def test_merge_snapshots():
    a = {"counters": {"x": 1, "y": 2.5}, "gauges": {"phase": "train"}}
    b = {"counters": {"x": 2}, "gauges": {"phase": "migrate"}}
    m = merge_snapshots([a, None, b])
    assert m["counters"] == {"x": 3, "y": 2.5}
    assert isinstance(m["counters"]["x"], int)
    # Unstamped snapshots keep the historical semantics: last input wins.
    assert m["gauges"]["phase"] == "migrate"
    assert merge_snapshots([]) == {"counters": {}, "gauges": {}}


def test_merge_snapshots_gauges_deterministic_by_ts():
    """Gauge merging is a function of snapshot CONTENTS, not input order:
    the newest ``(ts, host)`` stamp wins even when the caller (e.g.
    ``fleet_status`` globbing heartbeat files) iterates oldest-last or in
    filesystem order."""
    new = {"gauges": {"phase": "train"}, "ts": 200.0, "host": "h1"}
    old = {"gauges": {"phase": "boot"}, "ts": 100.0, "host": "h9"}
    for order in ([old, new], [new, old]):
        assert merge_snapshots(order)["gauges"]["phase"] == "train"
    # Wall-clock tie → host id breaks it, still order independent.
    a = {"gauges": {"g": "a"}, "ts": 50.0, "host": "hostA"}
    b = {"gauges": {"g": "b"}, "ts": 50.0, "host": "hostB"}
    for order in ([a, b], [b, a]):
        assert merge_snapshots(order)["gauges"]["g"] == "b"
    # Stamped beats unstamped regardless of position.
    stamped = {"gauges": {"g": "s"}, "ts": 1.0, "host": ""}
    unstamped = {"gauges": {"g": "u"}}
    for order in ([stamped, unstamped], [unstamped, stamped]):
        assert merge_snapshots(order)["gauges"]["g"] == "s"


def test_merge_snapshots_counter_properties():
    """Counter merging is associative and commutative (a derandomized
    hypothesis property): any merge tree over any permutation yields the
    same counter totals."""
    from hypothesis import given, settings
    from hypothesis import strategies as st

    keys = st.sampled_from(["a", "b", "c/d"])
    counters = st.dictionaries(keys, st.integers(0, 100), max_size=3)
    snap = counters.map(lambda c: {"counters": dict(c), "gauges": {}})

    @settings(max_examples=25, deadline=None)
    @given(snap, snap, snap)
    def check(x, y, z):
        left = merge_snapshots([merge_snapshots([x, y]), z])
        right = merge_snapshots([x, merge_snapshots([y, z])])
        flat = merge_snapshots([x, y, z])
        swapped = merge_snapshots([z, x, y])
        assert left["counters"] == right["counters"] == flat["counters"]
        assert swapped["counters"] == flat["counters"]

    check()


def test_registry_merge_across_processes(tmp_path):
    """A worker process's snapshot (as it rides in heartbeats) merges by
    summation with the local one."""
    code = (
        "import json, sys\n"
        "from repro.obs.registry import get_registry\n"
        "r = get_registry(); r.inc('ckpt/save', 4); r.set_phase('train')\n"
        "json.dump(r.snapshot(), sys.stdout)\n"
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)), check=True,
    )
    remote = json.loads(out.stdout)
    local = Registry()
    local.inc("ckpt/save")
    m = merge_snapshots([local.snapshot(), remote])
    assert m["counters"]["ckpt/save"] == 5
    assert m["gauges"]["phase"] == "train"


# ---------------------------------------------------------------------------
# Liveness / metrics hardening
# ---------------------------------------------------------------------------
def test_heartbeat_never_torn_under_concurrent_writers(tmp_path):
    """``beat`` (loop thread) and ``touch`` (refresher thread) race on one
    path: per-writer temp names mean a reader NEVER sees a torn file —
    which is exactly what keeps a live worker from being killed."""
    hb = Heartbeat(str(tmp_path / "heartbeat.json"), timeout=60.0)
    hb.beat(0)
    stop = threading.Event()
    errors = []

    def beater():
        i = 0
        while not stop.is_set():
            hb.beat(i, extra={"counters": {"loop/step": i}})
            i += 1

    def toucher():
        while not stop.is_set():
            hb.touch()

    def reader():
        while not stop.is_set():
            payload = hb.read()
            if payload is None:  # torn or vanished — the lethal case
                errors.append("torn/missing heartbeat observed")
            elif hb.status() not in ("alive",):
                errors.append(f"status {hb.status()}")

    threads = [threading.Thread(target=f)
               for f in (beater, toucher, reader, reader)]
    for t in threads:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join()
    assert not errors
    assert hb.status() == "alive"
    # No temp droppings left behind.
    assert [f for f in os.listdir(tmp_path) if f.endswith(".tmp")] == []


def test_heartbeat_touch_carries_phase(tmp_path):
    hb = Heartbeat(str(tmp_path / "hb.json"))
    get_registry().set_phase("migrate")
    hb.touch()
    assert hb.read()["phase"] == "migrate"
    assert hb.read()["step"] == 0  # touch never claims progress


def test_metrics_logger_one_device_get(tmp_path, monkeypatch):
    import repro.train.metrics as metrics_mod

    calls = []
    real = jax.device_get

    def counting(x):
        calls.append(x)
        return real(x)

    monkeypatch.setattr(metrics_mod.jax, "device_get", counting)
    path = str(tmp_path / "metrics.jsonl")
    reg = get_registry()
    reg.inc("ckpt/save", 2)
    with MetricsLogger(path) as lg:
        row = lg.log(0, {"loss": jax.numpy.float32(1.5),
                         "ceu": jax.numpy.float32(2.0)}, tokens=64)
        assert row["loss"] == 1.5
        assert len(calls) == 1  # ONE transfer for the whole row
        # Counter deltas ride the row from the host-side registry without
        # a second device transfer.
        assert row["delta/ckpt/save"] == 2
        reg.inc("ckpt/save")
        row1 = lg.log(1, {"loss": jax.numpy.float32(1.2),
                          "ceu": jax.numpy.float32(2.1)}, tokens=64)
        assert len(calls) == 2  # still one device_get PER ROW
        assert row1["delta/ckpt/save"] == 1
        row2 = lg.log(2, {"loss": jax.numpy.float32(1.1),
                          "ceu": jax.numpy.float32(2.2)}, tokens=64)
        # Unchanged counters emit no delta keys (rows stay tidy).
        assert "delta/ckpt/save" not in row2
        assert len(calls) == 3
    assert lg._f is None  # context manager closed the handle
    rows = [json.loads(line) for line in open(path)]
    assert [r["step"] for r in rows] == [0, 1, 2]
    assert rows[1]["tokens_per_s"] > 0


# ---------------------------------------------------------------------------
# fleet_status
# ---------------------------------------------------------------------------
def _mk_run_dir(tmp_path, name, hb=None, spec=None, events=(), metrics=(),
                done=None, torn_tail=False):
    d = tmp_path / name
    d.mkdir()
    if spec is not None:
        (d / "worker_spec.json").write_text(json.dumps(spec))
    if hb is not None:
        (d / "heartbeat.json").write_text(json.dumps(hb))
    if events or torn_tail:
        with open(d / "events.jsonl", "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")
            if torn_tail:
                f.write('{"time": 1.0, "host": "x", "event"')
    if metrics:
        with open(d / "metrics.jsonl", "w") as f:
            for m in metrics:
                f.write(json.dumps(m) + "\n")
    if done is not None:
        (d / "DONE.json").write_text(json.dumps(done))
    return str(d)


def test_fleet_status_json_on_synthetic_journals(tmp_path, capsys):
    from repro.launch import fleet_status as fs

    now = time.time()
    alive = _mk_run_dir(
        tmp_path, "alive",
        hb={"step": 7, "time": now, "phase": "train",
            "straggler_flagged": 1, "counters": {"ckpt/save": 3}},
        spec={"elastic": {"host_id": "host-a", "total_steps": 20,
                          "heartbeat_timeout_s": 300.0}},
        events=[{"time": now - 1, "host": "host-a",
                 "event": ["resume", 0, None, 8]}],
        metrics=[{"step": 7, "loss": 2.25}],
        torn_tail=True,
    )
    # Checkpoints: only dirs with a manifest count.
    os.makedirs(os.path.join(alive, "ckpt_00000004"))
    open(os.path.join(alive, "ckpt_00000004", "manifest.json"), "w").write(
        "{}"
    )
    os.makedirs(os.path.join(alive, "ckpt_00000006"))  # torn: no manifest

    stale = _mk_run_dir(
        tmp_path, "stale",
        hb={"step": 3, "time": now - 10_000, "phase": "train"},
    )
    dead = _mk_run_dir(tmp_path, "dead")  # no heartbeat at all
    done = _mk_run_dir(
        tmp_path, "done",
        hb={"step": 20, "time": now - 10_000},
        done={"step": 20, "loss": 1.5, "attempt": 2},
    )

    rc = fs.main(["--dir", alive, "--dir", stale, "--dir", dead,
                  "--dir", done, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    hosts = {h["host"]: h for h in doc["hosts"]}

    a = hosts["host-a"]  # named by worker_spec.json, not the dir
    assert a["status"] == "alive"
    assert a["step"] == 7 and a["total_steps"] == 20
    assert a["phase"] == "train"
    assert a["staleness_s"] < 60
    assert a["counters"] == {"ckpt/save": 3}
    assert a["ckpt_latest"] == 4 and a["ckpt_count"] == 1
    assert a["last_metrics"]["loss"] == 2.25
    assert a["recent_events"][-1]["event"] == ["resume", 0, None, 8]

    assert hosts["stale"]["status"] == "stale"
    assert hosts["stale"]["staleness_s"] > hosts["stale"][
        "heartbeat_timeout_s"]
    assert hosts["dead"]["status"] == "missing"
    assert hosts["dead"]["step"] is None
    assert hosts["done"]["status"] == "done"  # DONE trumps stale heartbeat
    assert hosts["done"]["step"] == 20

    # Human rendering of the same doc holds every host row.
    table = fs.render(doc)
    for name in ("host-a", "stale", "dead", "done"):
        assert name in table


def test_fleet_status_consensus_view(tmp_path, capsys):
    from repro.launch import fleet_status as fs
    from repro.train.fleet import FleetConfig, PlanConsensus, plan_digest

    fleet_dir = str(tmp_path / "fleet")
    plan = {"codec": "coap-plan/v1", "buckets": [1, 2]}
    a = PlanConsensus(FleetConfig(fleet_dir=fleet_dir, host_id="a"))
    b = PlanConsensus(FleetConfig(fleet_dir=fleet_dir, host_id="b"))
    a.beat()
    b.beat()
    got, role = a.plan_for_epoch("6:4x1024", lambda: plan)
    assert got == plan and role == "published"

    rc = fs.main(["--fleet-dir", fleet_dir, "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    fleet = doc["fleet"]
    assert fleet["n_alive"] == 2
    assert sorted(m["host"] for m in fleet["members"]) == ["a", "b"]
    cur = fleet["current_epoch"]
    assert cur["epoch"] == "6_4x1024"  # slugged
    assert cur["plan_digest"] == plan_digest(plan)
    assert cur["committed_by"] == "a"
    assert "digest " + plan_digest(plan)[:12] in fs.render(doc)


def test_fleet_status_requires_a_target():
    from repro.launch import fleet_status as fs

    with pytest.raises(SystemExit):
        fs.main(["--json"])


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------
def _toy_params():
    key = jax.random.key(3)
    mk = lambda i, shp: 0.3 * jax.random.normal(
        jax.random.fold_in(key, i), shp
    )
    return {"w1": mk(0, (64, 32)), "w2": mk(1, (64, 32)), "b": mk(2, (64,))}


def test_solver_bit_identical_without_artifact(tmp_path, monkeypatch):
    """No calibration artifact -> plans are bit-identical to an explicit
    analytic Calibration (the parity acceptance criterion). Pointing
    REPRO_COAP_CALIB at a nonexistent file pins the no-artifact path
    regardless of what lives under the repo's artifacts/."""
    monkeypatch.setenv("REPRO_COAP_CALIB", str(tmp_path / "absent.json"))
    params = _toy_params()
    p1 = solve(params, 10**12, **_KW)
    p2 = solve(params, 10**12, calib=Calibration.load(), **_KW)
    assert json.dumps(p1.to_dict(), sort_keys=True) == json.dumps(
        p2.to_dict(), sort_keys=True
    )
    assert p1.cost["calibration"]["hbm_bw"] == pytest.approx(819e9)


def test_calib_artifact_rescales_cost(tmp_path, monkeypatch):
    params = _toy_params()
    base = solve(params, 10**12, **_KW)
    art = str(tmp_path / "coap-calib.json")
    json.dump(
        {"codec": CALIB_CODEC, "hbm_bw": 819e9 / 4, "peak_flops": 197e12 / 4},
        open(art, "w"),
    )
    # Explicit path.
    c = Calibration.load(calib_path=art)
    assert c.hbm_bw == pytest.approx(819e9 / 4)
    assert ("hbm_bw", "coap-calib.json") in [tuple(s) for s in c.sources]
    slow = solve(params, 10**12, calib=c, **_KW)
    assert slow.cost["step_seconds"] == pytest.approx(
        4 * base.cost["step_seconds"]
    )
    # Env var consumption (what a traced run's artifact uses).
    monkeypatch.setenv("REPRO_COAP_CALIB", art)
    c_env = Calibration.load()
    assert c_env.hbm_bw == pytest.approx(819e9 / 4)


def test_calib_artifact_wrong_codec_ignored_and_loud(tmp_path):
    art = str(tmp_path / "bad.json")
    json.dump({"codec": "coap-calib/v999", "hbm_bw": 1.0}, open(art, "w"))
    c = Calibration.load(calib_path=art)  # silently-optional consumer
    assert c.hbm_bw == pytest.approx(819e9)  # analytic constant kept
    with pytest.raises(ValueError, match="coap-calib/v1"):
        obs_calib.load_calib(art)  # loud reader


def test_fit_nnls_recovers_constants():
    x_true, y_true = 1.0 / 800e9, 1.0 / 200e12
    samples = [
        {"bytes": b, "flops": f, "t": x_true * b + y_true * f}
        for b, f in [(1e9, 1e12), (2e9, 1e12), (1e9, 8e12), (4e9, 2e12)]
    ]
    x, y, res = obs_calib._fit_nnls_2(samples)
    assert x == pytest.approx(x_true, rel=1e-6)
    assert y == pytest.approx(y_true, rel=1e-6)
    assert res < 1e-12
    # Degenerate population (flops never varies the time): the fit falls
    # back to the better single-variable model, never negative.
    flat = [{"bytes": b, "flops": 0.0, "t": x_true * b}
            for b in (1e9, 2e9, 3e9)]
    x2, y2, _ = obs_calib._fit_nnls_2(flat)
    assert x2 == pytest.approx(x_true, rel=1e-6) and y2 == 0.0


def test_planned_refresh_schedule_matches_predicates():
    from repro.core.api import OptimizerConfig

    params = _toy_params()
    plan = solve(params, 10**12, **_KW)
    ocfg = OptimizerConfig(name="coap-adamw", learning_rate=1e-3)
    sched = obs_calib.planned_refresh_schedule(plan, params, ocfg)

    # Step 0: the mandatory whole-bucket Eqn-7 init, one event per bucket.
    ev0 = sched(0)
    assert ev0 and all(e["kind"] == "recal" and e["frac"] == 1.0
                       for e in ev0)
    t_u, lam = _KW["t_update"], _KW["lam"]
    seen_eqn6 = seen_recal = False
    for step in range(1, 2 * lam * t_u + 1):
        for e in sched(step):
            # Group refreshes exactly when its stagger predicate fires.
            assert (step + e["phase"]) % t_u == 0
            if (step + e["phase"]) % (lam * t_u) == 0:
                assert e["kind"] == "recal"
                seen_recal = True
            else:
                assert e["kind"] == "eqn6"
                seen_eqn6 = True
            assert 0 < e["frac"] <= 1.0
    assert seen_eqn6 and seen_recal


def test_build_from_trace_requires_samples(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = configure(path, host="h")
    with t.span("loop/step", step=0, compile=True):  # excluded from fit
        pass
    plan = solve(_toy_params(), 10**12, **_KW)
    with pytest.raises(ValueError, match="usable loop/step"):
        obs_calib.build_from_trace(path, plan, min_samples=4)


# ---------------------------------------------------------------------------
# End-to-end: traced elastic run -> Perfetto + calib + fleet_status
# ---------------------------------------------------------------------------
def test_traced_kill_shrink_resume_end_to_end(tmp_path, capsys):
    """THE acceptance scenario, traced: seeded kill at step 7 + topology
    shrink 8->4 at step 6 under a recording tracer. The trace must carry
    replan/restore/migrate/compile/step spans per attempt, export to a
    loadable Perfetto document, fit a coap-calib/v1 artifact the solver
    consumes via REPRO_COAP_CALIB, and fleet_status must report the run
    from the same directory."""
    from repro.configs import get_smoke
    from repro.data.synthetic import SyntheticLM
    from repro.launch import fleet_status as fs
    from repro.models.model import build_model
    from repro.train.elastic import (
        ElasticConfig,
        ElasticSupervisor,
        Topology,
    )
    from repro.train.faults import FaultInjector, FaultSchedule

    cfg = get_smoke("tinyllama-1.1b")
    model = build_model(cfg)
    data = SyntheticLM(vocab=cfg.vocab_size, order=1, noise=0.2)
    batch_fn = lambda step, host: data.batch(step, batch=4, seq=16,
                                             host=host)
    params = model.abstract_params()
    kw = dict(min_dim=16, t_update=4, lam=2, stagger_groups=2)
    from repro.plan.solver import solve_for_topology

    h32 = solve_for_topology(params, 1, 10**12, quantize="off",
                             **kw).predicted["hbm_total_bytes"]
    h8 = solve_for_topology(params, 1, 10**12, quantize="force",
                            **kw).predicted["hbm_total_bytes"]
    per_dev = (h32 + h8) // 2 // 4

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    trace_path = str(run_dir / "trace.jsonl")
    ecfg = ElasticConfig(
        ckpt_dir=str(run_dir),
        total_steps=12,
        topology=(Topology(8, per_dev), Topology(4, per_dev, from_step=6)),
        solve_kw=kw,
        ckpt_every=2,
        log_every=2,
        backoff_base=0.0,
        heartbeat_path=str(run_dir / "heartbeat.json"),
        metrics_path=str(run_dir / "metrics.jsonl"),
        events_path=str(run_dir / "events.jsonl"),
        trace_path=trace_path,
        host_id="host-e2e",
    )
    from repro.core.api import OptimizerConfig

    inj = FaultInjector(FaultSchedule(kill_at=(7,)), seed=0)
    sup = ElasticSupervisor(
        model, batch_fn, ecfg,
        ocfg=OptimizerConfig(name="coap-adamw", learning_rate=1e-3),
        fault_injector=inj,
    )
    state = sup.run()
    assert int(state.step) == 12
    assert [e[0] for e in sup.events] == ["resume", "crash", "migrate",
                                          "resume"]

    # -- the trace carries the full lifecycle --------------------------------
    rows = read_trace(trace_path)
    names = [r["name"] for r in rows]
    for required in ("elastic/attempt", "elastic/replan", "elastic/restore",
                     "elastic/migrate", "loop/step", "loop/checkpoint"):
        assert required in names, f"missing span {required}"
    steps = [r for r in rows if r["name"] == "loop/step"]
    # Two attempts -> two compile-tagged first steps. Attempt 1 ran steps
    # 0..6 (killed entering 7), attempt 2 resumed the step-6 checkpoint
    # and ran 6..11.
    compiles = [r for r in steps if (r.get("attrs") or {}).get("compile")]
    assert len(compiles) == 2
    assert sorted(r["attrs"]["step"] for r in steps) == sorted(
        list(range(7)) + list(range(6, 12))
    )
    # Refresh attribution present: step 0 carries the whole-bucket init.
    s0 = next(r for r in steps if r["attrs"]["step"] == 0)
    assert s0["attrs"]["refresh"][0]["frac"] == 1.0
    resumes = [r for r in rows if r["name"] == "elastic/resume"]
    assert [(r["attrs"]["attempt"], r["attrs"]["n_devices"])
            for r in resumes] == [(0, 8), (1, 4)]

    # -- Perfetto export -----------------------------------------------------
    doc = export_perfetto(trace_path, str(run_dir / "perfetto.json"))
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {"elastic/migrate", "loop/step"} <= {e["name"] for e in xs}
    assert all("dur" in e and e["ts"] > 0 for e in xs)

    # -- fit + consume the calibration artifact ------------------------------
    plan4 = sup.plan_for(Topology(4, per_dev, from_step=6))
    art_path = str(run_dir / "coap-calib.json")
    artifact = obs_calib.build_from_trace(trace_path, plan4,
                                          out_path=art_path)
    assert artifact["codec"] == CALIB_CODEC
    assert artifact["n_samples"] >= 10  # 13 step spans minus 2 compiles
    assert artifact["n_refresh_samples"] >= 1
    assert artifact["hbm_bw"] or artifact["peak_flops"]
    os.environ["REPRO_COAP_CALIB"] = art_path
    try:
        calibrated = Calibration.load()
        fitted = solve(params, 10**12, calib=calibrated, **kw)
    finally:
        del os.environ["REPRO_COAP_CALIB"]
    assert any("coap-calib.json" in s[1]
               for s in fitted.cost["calibration_sources"])
    assert fitted.cost["step_seconds"] > 0

    # -- fleet_status over the same directory --------------------------------
    rc = fs.main(["--dir", str(run_dir), "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    h = out["hosts"][0]
    assert h["status"] == "alive"  # heartbeat still fresh
    assert h["step"] == 11  # last in-loop beat (final ckpt comes after)
    assert h["phase"] == "train"
    assert h["counters"]["ckpt/save"] >= 1
    assert h["ckpt_latest"] == 12
    assert h["last_metrics"]["loss"] > 0
    kinds = [e["event"][0] for e in h["recent_events"]]
    assert "migrate" in kinds and "resume" in kinds
