"""BENCHMARK.json and the files it names: shape, names, and that every
configuration and traffic mix resolves against the program's registry."""
import dataclasses
import json
import re

import jax
import pytest

from bench import harness
from bench.references import coap_adamw

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_units_and_keys():
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", m["unit"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            assert (group, entry["name"]) not in names
            names.add((group, entry["name"]))
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_its_metrics_move(cell):
    c = harness.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_resolves_against_the_registry(conf):
    from repro.configs import get_config
    from repro.models.model import build_model

    data = json.loads((harness.ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"] and data["source"] == conf["source"]
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    for key, cut in data["reduced"].items():
        assert data["config"][key] == cut["run"] != cut["published"]
    fields = harness.arch_fields(data)
    cfg = dataclasses.replace(get_config(data["registry_arch"]), **fields)
    for k, v in fields.items():
        assert getattr(cfg, k) == v
    got = {coap_adamw.path_of(kp): tuple(x.shape) for kp, x in
           jax.tree_util.tree_flatten_with_path(build_model(cfg).abstract_params())[0]}
    assert got == harness.reference_module(data).layout(fields)


DENSE = [c for c in SPEC["configs"] if json.loads((harness.ROOT / c["file"]).read_text())
         ["reference"] == "bench/references/dense_gqa.py"]


@pytest.mark.parametrize("conf", DENSE, ids=lambda c: c["name"])
def test_dense_config_states_the_programs_head_dim(conf):
    """The dense reference sizes its heads by ``head_dim``; the program
    resolves the same."""
    from repro.configs import get_config

    data = json.loads((harness.ROOT / conf["file"]).read_text())
    fields = harness.arch_fields(data)
    cfg = dataclasses.replace(get_config(data["registry_arch"]), **fields)
    assert cfg.resolved_head_dim == fields["head_dim"]


@pytest.mark.parametrize("cell", CELLS)
def test_stated_phases_are_the_programs(cell):
    """The reference refreshes each matrix at the phase the traffic states;
    the program's optimizer staggers its refreshes at the same phases."""
    from bench.tests import tiny

    c = harness.load_cell(cell)
    o = c.traffic["optimizer"]
    shapes = c.reference.layout(harness.arch_fields(c.config))
    assert o["phases"] == tiny.program_phases(shapes, o)


@pytest.mark.parametrize("cell", CELLS)
def test_compared_steps_hold_a_refresh_of_each_kind(cell):
    """The compared steps hold the first step's Eqn-7 initialisation and at
    least one Eqn-6 refresh after it."""
    c = harness.load_cell(cell)
    o = c.traffic["optimizer"]
    kinds = [dict(coap_adamw.refreshes(k, o)) for k in range(c.traffic["compared_steps"])]
    assert set(kinds[0]) == set(o["phases"]) and set(kinds[0].values()) == {"eqn7"}
    assert any("eqn6" in k.values() for k in kinds[1:]), kinds


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_sizes(cell):
    t = harness.load_cell(cell).traffic
    assert t["compared_steps"] <= t["warmup_steps"] <= t["distinct_steps"]
    assert t["trace_steps"] >= 1
    assert t["optimizer"]["quantize"] == t["optimizer"]["name"].startswith("8bit-")
