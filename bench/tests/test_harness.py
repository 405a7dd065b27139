"""The harness on the CPU: it refuses the CPU, finds a cell it is given as
files alone, reads the model through the reference module its
configuration names, and a run comes out correct, and not correct with
the timed path broken underneath it."""
import json

import pytest

from bench import harness
from bench.tests import tiny


def test_refuses_a_cpu_device(capsys):
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.check_device(1)
    cell = json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    rc = harness.main(["--workload", cell["name"], "--seed", "1", "--seconds", "1"],
                      t0=0.0)
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "no TPU" in err


def test_refuses_an_unknown_device_kind():
    with pytest.raises(harness.BenchError, match="peaks.json"):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["bf16_flop_per_s"] == 197e12


def test_every_cell_resolves():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.traffic["kind"] == "train"
        assert set(cell.limits) == {"loss", "grad", "update", "grad_diff"}
        assert {m["name"] for m in cell.end_to_end} >= {"tokens_per_s", "setup_s"}
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.metric_reader(m["name"]))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_a_cell_added_as_files_is_found(root):
    cell = harness.load_cell(tiny.CELL, root)
    assert cell.config["name"] == "tiny"
    assert harness.arch_fields(cell.config)["d_model"] == 64
    # the metrics that list no cells are reported by the new cell too
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s", "step_ms_p90", "peak_hbm_gib", "setup_s"}
    assert {"eqn6_unfused_buckets", "opt_update_ms", "bucket_copy_ms",
            "refresh_ms"} <= {m["name"] for m in cell.per_layer}
    assert cell.reference.__file__ == str(root / cell.config["reference"])


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_a_metric_that_lists_its_cells_is_reported_by_those_alone(tmp_path, group):
    root = tiny.make_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    other = next(w["name"] for w in spec["workloads"] if w["name"] != tiny.CELL)
    listed = dict(spec[group][0], name="listed_elsewhere", workloads=[other])
    spec[group].append(listed)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    def names(cell):
        return {m["name"] for m in getattr(harness.load_cell(cell, root), group)}

    assert "listed_elsewhere" in names(other)
    assert "listed_elsewhere" not in names(tiny.CELL)
    assert spec[group][0]["name"] in names(tiny.CELL)


def test_run_is_correct(root):
    result = tiny.run(root)
    assert result["correct"], result["check"]
    assert list(result)[-1] == "check"
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "step_ms_p90", "peak_hbm_gib",
                                      "setup_s"}


def _unchanged_state(monkeypatch):
    from repro.train import loop as loop_mod

    real = loop_mod.make_train_step

    def broken(model, tx, **kw):
        step = real(model, tx, **kw)

        def same_state(state, batch):
            new, metrics = step(state, batch)
            return state._replace(step=new.step), metrics

        return same_state

    monkeypatch.setattr(loop_mod, "make_train_step", broken)


def _half_batch(monkeypatch):
    from repro.models.model import LMModel

    real = LMModel.loss

    def half(self, params, batch):
        n = batch["tokens"].shape[0] // 2
        return real(self, params, {k: v[:n] for k, v in batch.items()})

    monkeypatch.setattr(LMModel, "loss", half)


def _moved_double(monkeypatch):
    from repro.train import loop as loop_mod

    real = loop_mod.make_train_step

    def broken(model, tx, **kw):
        step = real(model, tx, **kw)

        def double(state, batch):
            new, metrics = step(state, batch)
            attn = dict(new.params["stack"]["attn"])
            old = state.params["stack"]["attn"]["wq"]
            attn["wq"] = old + 2 * (attn["wq"] - old)
            params = dict(new.params, stack=dict(new.params["stack"], attn=attn))
            return new._replace(params=params), metrics

        return double

    monkeypatch.setattr(loop_mod, "make_train_step", broken)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch, _moved_double],
                         ids=["state_unchanged", "half_batch", "moved_double"])
def test_a_broken_step_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    result = tiny.run(root)
    assert not result["correct"], result["check"]


def test_refuses_a_loop_without_the_fields_it_sets(root, monkeypatch):
    from repro.train.loop import TrainLoop

    real = TrainLoop.__init__

    def renamed(self, *a, **kw):
        real(self, *a, **kw)
        self._start_state = vars(self).pop("_initial_state")

    monkeypatch.setattr(TrainLoop, "__init__", renamed)
    with pytest.raises(RuntimeError, match="_initial_state"):
        tiny.run(root)


def test_refuses_a_second_state_on_the_device(root, monkeypatch):
    from bench import train_cell

    def keeps_state(self, step, host):
        self.marks.append((step, 0.0))
        return self.batches[step % len(self.batches)]

    monkeypatch.setattr(train_cell.TrainCell, "batch_fn", keeps_state)
    with pytest.raises(RuntimeError, match="live on the device"):
        tiny.run(root)


# A configuration brings its model as files: the module its ``reference``
# names, written into the checkout beside it.
REEXPORT = "from bench.references.dense_gqa import *  # noqa: F401,F403\n"


def test_a_reference_named_by_the_configuration_runs_correct(tmp_path):
    root = tiny.make_root(tmp_path, reference=REEXPORT)
    cell = harness.load_cell(tiny.CELL, root)
    assert cell.config["reference"] == tiny.PROBE
    assert cell.reference.__file__ == str(root / tiny.PROBE)
    result = tiny.run(root)
    assert result["correct"], result["check"]


def test_the_named_reference_loss_decides(tmp_path):
    scaled = REEXPORT + (
        "from bench.references import dense_gqa as _dense\n\n\n"
        "def loss(params, tokens, labels, arch, rnd=_dense._identity):\n"
        "    return 1.5 * _dense.loss(params, tokens, labels, arch, rnd)\n")
    result = tiny.run(tiny.make_root(tmp_path, reference=scaled))
    check = result["check"]
    assert not result["correct"], check
    # the first gradient is clipped to norm 1 on both sides: only the loss
    # can tell the scaled reference from the program
    assert check["loss"]["value"] > check["loss"]["limit"], check


def test_refuses_a_reference_whose_layout_differs_from_the_program(tmp_path):
    short = REEXPORT + (
        "from bench.references import dense_gqa as _dense\n\n\n"
        "def layout(arch):\n"
        "    shapes = _dense.layout(arch)\n"
        "    del shapes['final_norm']\n"
        "    return shapes\n")
    root = tiny.make_root(tmp_path, reference=short)
    with pytest.raises(harness.BenchError, match=f"layout of {tiny.PROBE}"):
        tiny.run(root)


@pytest.mark.parametrize("conf, match", [
    ({"name": "x"}, "names no reference"),
    ({"name": "x", "reference": "bench/references/absent.py"}, "is not at"),
    ({"name": "x", "reference": "bench/references/coap_adamw.py"}, "does not export"),
], ids=["unnamed", "absent", "no_interface"])
def test_refuses_a_reference_it_cannot_read(conf, match):
    with pytest.raises(harness.BenchError, match=match):
        harness.reference_module(conf)
