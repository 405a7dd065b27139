"""The trace reduction, read on a small trace recorded on a TPU v5e.

``data/toy_train.xplane.pb.gz`` was recorded by ``record_trace.py``: a
toy model trains three steps with each COAP optimizer inside the
``bench/window`` span.
"""
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data" / "toy_train.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "toy_train.xplane.pb"
    path.write_bytes(gzip.decompress(DATA.read_bytes()))
    return trace_reduce.reduce_file(str(path))


def test_window_and_busy(reduced):
    assert reduced.chips == 1
    assert 0.03 < reduced.window_s < 0.06  # the recorded span: 41.2 ms
    assert 0 < reduced.busy_s < reduced.window_s
    # busy is the union: no more than the summed op time, no less than the
    # longest op
    total = sum(op.dur for op in reduced.ops) * 1e-9
    assert max(op.dur for op in reduced.ops) * 1e-9 <= reduced.busy_s <= total


def test_both_fused_kernels_found(reduced):
    # one launch per projected bucket per step: 5 buckets x 3 steps each
    for name in ("coap_fused_update_bp_pallas", "coap_fused_update_q8_pallas"):
        ops = reduced.matching(name)
        assert len(ops) == 15, (name, len(ops))
        assert all(op.dur > 0 for op in ops)


def test_op_names_are_instruction_names(reduced):
    names = {op.name for op in reduced.ops}
    assert all(" " not in n and not n.startswith("%") for n in names)


def test_breakdown(reduced):
    b = reduced.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)
    gaps = [t for _, t in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= reduced.window_s - reduced.busy_s + 1e-9
    assert all(isinstance(label, str) and label for label, _ in b["idle_gaps"])


def test_union_of_intervals():
    got = trace_reduce._union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)])
    assert got == [[0, 3], [5, 9], [10, 11]]
