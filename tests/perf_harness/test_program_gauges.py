"""The three `hbm_*` metrics (ISSUE 38): the manifest resolves them, and
their reader takes the program's gauges over the traced window, or nothing
where there is nothing to read.

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
CELLS = [w["name"] for w in BENCH["workloads"]]
# metric -> (better, layer, the gauge read, its stat, the cells)
GAUGE_METRICS = {
    # free LESS the widest step's temporaries, which only a cell that
    # compiles its steps ahead knows: `big.train` reads nothing
    "hbm_free_min_share.train": ("higher", "device", "hbm.headroom", "min",
                                 CELLS[1:4]),
    "hbm_peak_share.train": ("lower", "device", "hbm.peak", "last",
                             CELLS[:4]),
    "hbm_step_programs_share.train": ("lower", "train step",
                                      "hbm.programs_code", "last",
                                      CELLS[1:4]),
}
TRACED = {"trace": {"window_s": 3.0}}
# what a traced window leaves in TRACER.gauges(): name -> [last, min, max, n]
PLANTED = {"hbm.free": [6e9, 5e9, 7e9, 5], "hbm.limit": [16e9, 16e9, 16e9, 1],
           "hbm.headroom": [15e8, 5e8, 25e8, 5],
           "hbm.peak": [10e9, 10e9, 10e9, 1],
           "hbm.programs_code": [4e8, 4e8, 4e8, 5]}


@pytest.fixture
def tracer():
    from marian_tpu import obs
    obs.TRACER.reset()
    yield obs.TRACER
    obs.TRACER.reset()


@pytest.mark.parametrize("name", sorted(GAUGE_METRICS))
def test_metric_resolves_and_names_its_cells(name):
    better, layer, gauge, stat, cells = GAUGE_METRICS[name]
    assert manifest.validate(BENCH) == []
    entry = [m for m in BENCH["per_layer"] if m["name"] == name][0]
    assert entry == {"name": name, "unit": "%", "better": better,
                     "source": "program_counter", "layer": layer,
                     "moves": "train_tok_s_chip", "workloads": cells}
    assert set(entry["workloads"]) <= set(CELLS)
    spec = manifest.load_layer_metric(name)
    assert spec["reader"] == "program_gauge"
    assert spec["args"] == {"gauge": gauge, "stat": stat,
                            "over": "hbm.limit", "scale": 100.0}
    assert callable(manifest.load_reader(spec["reader"]).read)


def test_nothing_without_a_trace(tracer):
    read = manifest.load_reader("program_gauge").read
    tracer._gauges = {k: list(v) for k, v in PLANTED.items()}
    args = {"gauge": "hbm.free", "stat": "min"}
    assert read({"trace": None}, args) is None          # --trace 0
    assert read({}, args) is None
    assert read(TRACED, args) == 5e9


def test_nothing_on_a_program_without_gauges(monkeypatch):
    """The parent commit's tracer keeps no gauges: the metric is left out
    of the line, nothing raises."""
    from marian_tpu import obs
    read = manifest.load_reader("program_gauge").read

    class OldTracer:
        enabled = False

        def counters(self):
            return {}
    monkeypatch.setattr(obs, "TRACER", OldTracer())
    assert read(TRACED, {"gauge": "hbm.free", "stat": "min",
                         "over": "hbm.limit"}) is None


def test_nothing_for_a_gauge_never_written(tracer):
    """A device without memory statistics, or a cell that compiles no
    step ahead: the gauge, or its divisor, is not there."""
    read = manifest.load_reader("program_gauge").read
    assert tracer.gauges() == {}
    args = {"gauge": "hbm.programs_code", "stat": "last",
            "over": "hbm.limit", "scale": 100.0}
    assert read(TRACED, args) is None
    tracer._gauges = {"hbm.programs_code": [4e8, 4e8, 4e8, 5]}
    assert read(TRACED, args) is None                   # no divisor
    tracer._gauges["hbm.limit"] = [0, 0, 0, 1]
    assert read(TRACED, args) is None                   # a limit of 0


@pytest.mark.parametrize("name, want", [
    ("hbm_free_min_share.train", 100 * 5e8 / 16e9),
    ("hbm_peak_share.train", 100 * 10e9 / 16e9),
    ("hbm_step_programs_share.train", 100 * 4e8 / 16e9),
])
def test_reader_gives_the_quotient_of_planted_gauges(tracer, name, want):
    tracer._gauges = {k: list(v) for k, v in PLANTED.items()}
    spec = manifest.load_layer_metric(name)
    read = manifest.load_reader(spec["reader"]).read
    assert read(TRACED, spec["args"]) == pytest.approx(want)
    assert 0 < read(TRACED, spec["args"]) <= 100
    # the other stats of the same gauge, where a metric file asks for them
    assert read(TRACED, dict(spec["args"], stat="max")) >= \
        read(TRACED, dict(spec["args"], stat="min"))


def test_gauges_written_through_the_tracer_are_read(tracer):
    """End to end on the host: what Tracer.gauge keeps is what the reader
    divides."""
    tracer.enable()
    for headroom in (900, 300, 600):
        tracer.gauge("hbm.headroom", headroom)
    tracer.gauge("hbm.limit", 1200)
    spec = manifest.load_layer_metric("hbm_free_min_share.train")
    read = manifest.load_reader(spec["reader"]).read
    assert read(TRACED, spec["args"]) == pytest.approx(25.0)
    # the widest step's temporaries exceed what is free: below 0, as it is
    tracer.gauge("hbm.headroom", -60)
    assert read(TRACED, spec["args"]) == pytest.approx(-5.0)


def test_the_headroom_metric_does_not_copy_the_peak(tracer):
    """What REVIEW 38 found: 100 x min `hbm.free` / `hbm.limit` was 100
    less `hbm_peak_share.train` to four digits in every cell. The metric
    reads the gauge that has the temporaries taken off, and nothing where
    no program ledger gives them (the jitted step of `big.train`)."""
    spec = manifest.load_layer_metric("hbm_free_min_share.train")
    read = manifest.load_reader(spec["reader"]).read
    tracer._gauges = {k: list(v) for k, v in PLANTED.items()
                      if k != "hbm.headroom"}
    assert read(TRACED, spec["args"]) is None
    tracer._gauges = {k: list(v) for k, v in PLANTED.items()}
    peak = manifest.load_layer_metric("hbm_peak_share.train")
    assert read(TRACED, spec["args"]) + read(TRACED, peak["args"]) < 70
