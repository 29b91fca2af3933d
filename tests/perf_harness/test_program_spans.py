"""The five `program_span` metrics of `big.train` (ISSUE 24): the manifest
resolves them, and their reader takes the program's own span totals over
the traced window, or nothing where there is nothing to read.

    JAX_PLATFORMS=cpu python -m pytest tests/perf_harness -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest  # noqa: E402

BENCH = manifest.load_benchmark()
SPAN_METRICS = {
    "host_busy_share.train": ["train.h2d", "train.dispatch",
                              "train.bookkeep"],
    "h2d_share.train": ["train.h2d"],
    "dispatch_share.train": ["train.dispatch"],
    "sync_wait_share.train": ["train.sync"],
    "batch_wait_share.train": ["data.wait"],
}
# what a traced window of 3 s would leave in TRACER.totals():
# name -> (calls, seconds, self seconds)
PLANTED = {"train.h2d": (27, 0.03, 0.03), "train.dispatch": (27, 0.06, 0.06),
           "train.bookkeep": (27, 2.73, 0.03), "train.sync": (3, 2.7, 2.7),
           "data.wait": (28, 0.003, 0.003)}


def test_manifest_is_sound_with_the_five_entries():
    assert manifest.validate(BENCH) == []
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SPAN_METRICS:
        m = entries[name]
        assert (m["source"], m["unit"], m["layer"], m["moves"]) == (
            "program_span", "%", "trainer loop", "train_tok_s_chip")
        assert m["workloads"] == ["big.train"]
    assert entries["sync_wait_share.train"]["better"] == "higher"
    # appended: what the benchmark had keeps its place
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[-5:] == list(SPAN_METRICS) and len(names) == 11


@pytest.mark.parametrize("name", sorted(SPAN_METRICS))
def test_metric_file_names_its_spans(name):
    spec = manifest.load_layer_metric(name)
    assert spec["reader"] == "program_span_share"
    assert spec["args"] == {"spans": SPAN_METRICS[name], "self": True}


@pytest.fixture
def planted():
    from marian_tpu import obs
    obs.TRACER.reset()
    yield obs.TRACER
    obs.TRACER.reset()


def test_reader_returns_nothing_without_trace_or_totals(planted):
    read = manifest.load_reader("program_span_share").read
    args = {"spans": ["train.sync"], "self": True}
    assert planted.totals() == {}
    assert read({"trace": {"window_s": 3.0}}, args) is None   # no totals
    planted._totals = {"train.sync": [3, 2.7, 2.7, "MainThread"]}
    assert read({"trace": None}, args) is None                # --trace 0
    assert read({}, args) is None
    assert read({"trace": {"window_s": 0.0}}, args) is None


def test_reader_returns_nothing_on_a_program_without_totals(monkeypatch):
    """The parent commit's tracer keeps no totals: the metric is left out
    of the line, nothing raises."""
    from marian_tpu import obs
    read = manifest.load_reader("program_span_share").read

    class OldTracer:
        enabled = False
    monkeypatch.setattr(obs, "TRACER", OldTracer())
    assert read({"trace": {"window_s": 3.0}},
                {"spans": ["train.sync"], "self": True}) is None


@pytest.mark.parametrize("name, want", [
    ("host_busy_share.train", 100 * 0.12 / 3.0),   # self: sync not twice
    ("h2d_share.train", 1.0),
    ("dispatch_share.train", 2.0),
    ("sync_wait_share.train", 90.0),
    ("batch_wait_share.train", 0.1),
])
def test_reader_gives_the_share_of_planted_totals(planted, name, want):
    planted._totals = {n: [c, s, own, "MainThread"]
                       for n, (c, s, own) in PLANTED.items()}
    spec = manifest.load_layer_metric(name)
    got = manifest.load_reader(spec["reader"]).read(
        {"trace": {"window_s": 3.0, "busy_s": 2.98}}, spec["args"])
    assert got == pytest.approx(want)
    # total seconds where a metric file asks for them
    whole = manifest.load_reader(spec["reader"]).read(
        {"trace": {"window_s": 3.0}}, dict(spec["args"], self=False))
    assert whole >= got
