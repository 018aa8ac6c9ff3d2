"""Each reader on a canned run: data/records_<kind>.json are the ``records``
of one run of a runner kind (as ``.runs/<cell>/result.json`` keeps them)."""

import json
import os

import pytest

from chipbench.run import _reader, per_layer_metrics

HERE = os.path.dirname(__file__)
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def records(kind):
    return json.load(open(os.path.join(HERE, "data", f"records_{kind}.json")))


WANT = {
    "steady_steps": {
        "step_ms_p50": 128.0, "mfu": 100.0 * 1e9 * 49.25e3 / 197e12,
        "device_idle_share.train": 100.0 * (1 - 2.94 / 3.0),
        "peak_hbm_gb": 6.8,
    },
}


READERS = sorted(f[:-3] for f in os.listdir(os.path.join(
    ROOT, "chipbench", "readers")) if f.endswith(".py"))


@pytest.mark.parametrize("kind", sorted(WANT))
def test_every_reader_on_a_canned_run(kind):
    rec = records(kind)
    for name in READERS:
        value = _reader(name).read(rec)
        if name in WANT[kind]:
            assert value == pytest.approx(WANT[kind][name]), name
        else:  # nothing of that metric in this kind of run: left out
            assert value is None, name


def test_every_reader_reads_nothing_from_an_empty_run():
    for name in READERS:
        assert _reader(name).read({}) is None, name


def test_every_metric_has_a_reader():
    assert {m["name"] for m in BENCH["per_layer"]} == set(READERS)


def test_a_cell_s_line_holds_its_own_metrics_only():
    got = per_layer_metrics(BENCH, "transformer-base.steady",
                            records("steady_steps"))
    assert set(got) == set(WANT["steady_steps"])
    assert got["mfu"]["unit"] == "%"
