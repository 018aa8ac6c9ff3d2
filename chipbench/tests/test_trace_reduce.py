"""trace_reduce on a small recorded trace: data/trace_events.json holds
the device and host events of a traced slice as ``load`` returns them."""

import json
import os

import pytest

from chipbench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_events.json")


def test_union_merges_nested_and_touching():
    ev = [("while", 0.0, 10.0), ("fusion.1", 1.0, 2.0), ("fusion.2", 3.0, 1.0),
          ("copy", 12.0, 1.0), ("copy", 13.0, 0.5), ("zero", 20.0, 0.0)]
    assert trace_reduce.union(ev) == [(0.0, 10.0), (12.0, 13.5)]
    assert trace_reduce.busy_seconds({"/device:TPU:0": ev}) == 11.5


def test_busy_is_averaged_over_chips():
    planes = {"/device:TPU:0": [("a", 0.0, 2.0)],
              "/device:TPU:1": [("a", 0.0, 4.0)]}
    assert trace_reduce.busy_seconds(planes) == 3.0
    assert trace_reduce.busy_seconds({}) == 0.0


def test_gaps_go_to_the_span_that_covers_most():
    ev = [("a", 0.0, 1.0), ("b", 2.0, 1.0), ("c", 6.0, 1.0), ("d", 7.5, 1.0)]
    spans = [("dispatch_step", 0.9, 1.0), ("slice_and_shard_batch", 3.1, 2.0),
             ("wait_for_the_device", 5.1, 0.5)]
    gaps = dict(trace_reduce.idle_gaps(ev, spans))
    assert gaps == {"slice_and_shard_batch": 3.0, "dispatch_step": 1.0,
                    "unattributed": 0.5}


def test_op_table_sums_by_name_and_keeps_the_top():
    planes = {"p": [("x", 0, 1.0), ("y", 1, 3.0), ("x", 4, 1.5)]}
    assert trace_reduce.op_table(planes, top=1) == [["y", 3.0]]
    assert trace_reduce.op_table(planes) == [["y", 3.0], ["x", 2.5]]


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace():
    rec = json.load(open(DATA))
    devices = {k: [tuple(e) for e in v] for k, v in rec["devices"].items()}
    spans = [tuple(e) for e in rec["spans"]]
    busy = trace_reduce.busy_seconds(devices)
    first = next(iter(devices.values()))
    extent = max(s + d for _, s, d in first) - min(s for _, s, _ in first)
    gaps = trace_reduce.idle_gaps(first, spans)
    assert 0 < busy <= extent
    assert busy + sum(s for _, s in gaps) == pytest.approx(extent, rel=1e-6)
    assert busy == pytest.approx(rec["expected"]["busy_s"], rel=1e-9)
    assert trace_reduce.op_table(devices)[0][0] == rec["expected"]["top_op"]
