"""What a later PR may do with files and entries alone, held on a cell
that is in no benchmark: `fixture_cell/` is a decoder-only, one-stream
configuration cut in depth, with its plain reference, its traffic mix, a
per-layer metric and cost functions of its own, laid out like benchmark/.
The manifest takes it beside the benchmark's own cell, tells a cut that
is not declared in full, resolves cost functions by `module:function`, and
the UNCHANGED train driver rehearses it to `correct=True`.

    JAX_PLATFORMS=cpu python -m pytest tests/perf_harness -q
"""

import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import corpus, kernel_costs, manifest  # noqa: E402
from test_harness import (  # noqa: E402  (pytest puts this directory first)
    BENCH, FIXTURE, FIXTURE_BENCH, _rehearse)

CELL = FIXTURE_BENCH["workloads"][0]["name"]
OWN_METRIC = "decoder_attention_roofline.lm"


def merged():
    """BENCHMARK.json with the fixture's configuration, cell and metric
    added as a later PR would add them: entries appended, the cell's name
    in the `workloads` of each metric it reports."""
    joins = {m["name"] for m in FIXTURE_BENCH["end_to_end"]
             + FIXTURE_BENCH["per_layer"]}
    both = json.loads(json.dumps(BENCH))
    both["configs"] += FIXTURE_BENCH["configs"]
    both["workloads"] += FIXTURE_BENCH["workloads"]
    for m in both["end_to_end"] + both["per_layer"]:
        if "workloads" in m and m["name"] in joins:
            m["workloads"].append(CELL)
    both["per_layer"] += [m for m in FIXTURE_BENCH["per_layer"]
                          if m["name"] == OWN_METRIC]
    return both


def edited_fixture(tmp_path, edit):
    """A copy of the fixture whose configuration file `edit` changes;
    returns (manifest, root)."""
    root = str(tmp_path / "fixture_cell")
    shutil.copytree(FIXTURE, root)
    bench = manifest.load_benchmark(root)
    entry = bench["configs"][0]
    path = os.path.join(root, "configs", f"{entry['name']}.json")
    entry["file"] = os.path.relpath(path, ROOT)
    with open(path, encoding="utf-8") as fh:
        body = json.load(fh)
    edit(body, entry)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(body, fh)
    return bench, root


# -- the manifest ---------------------------------------------------------------

def test_the_fixture_is_cut_one_stream_and_outside_the_benchmark():
    assert manifest.validate(FIXTURE_BENCH, FIXTURE) == []
    cell = manifest.Cell(FIXTURE_BENCH, CELL, FIXTURE)
    assert cell.config["reduced"] == ["dec_depth"]
    assert cell.config["published"] == {"dec_depth": 6}
    assert cell.config["streams"] == 1 and cell.kind == "train"
    assert "--type" in cell.config["task_flags"] \
        and "transformer-lm" in cell.config["task_flags"]
    assert CELL not in [w["name"] for w in BENCH["workloads"]]
    # every file the cell names lies outside benchmark/
    for parts in (("configs", "lm-base-cut.json"),
                  ("configs", "lm_reference.py"), ("configs", "lm_costs.py"),
                  ("traffic", "train-docs.json"),
                  ("layer_metrics", f"{OWN_METRIC}.json")):
        assert os.path.isfile(os.path.join(FIXTURE, *parts))
        assert not os.path.exists(os.path.join(manifest.BENCH_DIR, *parts))


def test_a_twelfth_metric_and_a_second_cell_on_the_span_metrics_are_sound():
    both = merged()
    assert manifest.validate(both, FIXTURE) == []
    assert len(both["per_layer"]) == len(BENCH["per_layer"]) + 1
    big = manifest.Cell(both, "big.train", FIXTURE)
    lm = manifest.Cell(both, CELL, FIXTURE)
    spans = [m["name"] for m in both["per_layer"]
             if m["source"] == "program_span"]
    assert len(spans) == 5
    for cell in (big, lm):
        assert set(spans) <= {m["name"] for m in cell.per_layer}
    assert OWN_METRIC in {m["name"] for m in lm.per_layer}
    assert OWN_METRIC not in {m["name"] for m in big.per_layer}
    # the benchmark's own cell reads as before
    assert [m["name"] for m in big.per_layer] == [
        m["name"] for m in manifest.Cell(BENCH, "big.train").per_layer]
    assert big.config == manifest.load_config("transformer-big")


def test_a_root_is_looked_in_first_and_benchmark_after():
    assert manifest.load_traffic("train-docs", FIXTURE)["kind"] == "train"
    assert manifest.load_traffic("train", FIXTURE) == \
        manifest.load_traffic("train")
    with pytest.raises(manifest.ManifestError, match="train-docs"):
        manifest.load_traffic("train-docs")
    ref = manifest.load_reference("lm_reference", FIXTURE)
    assert ref is manifest.load_reference("lm_reference", FIXTURE)
    assert manifest.load_reference("transformer_reference", FIXTURE) is \
        manifest.load_reference("transformer_reference")


@pytest.mark.parametrize("edit, told", [
    (lambda body, entry: None, None),            # the copy itself is sound
    (lambda body, entry: body.pop("published"),
     "cuts 'dec_depth' without its published value"),
    (lambda body, entry: body.update(published={"dec_depth": 2}),
     "lists 'dec_depth' as cut and runs the published value"),
    (lambda body, entry: body.pop("dec_depth"),
     "cuts 'dec_depth', which is no key of its file"),
    (lambda body, entry: entry.update(reduced=[]),
     "reduced differs from its file"),
    (lambda body, entry: body.update(reduced=["dec_depth", "vocab"]),
     "reduced differs from its file"),
    (lambda body, entry: body.update(deployment={"chips": 0, "how": "x"}),
     "deployment wants chips"),
    (lambda body, entry: body.update(deployment={"chips": 32}),
     "deployment wants chips"),
    (lambda body, entry: body.pop("kernels"),
     "lacks ['kernels'], which drivers/train.py reads"),
    (lambda body, entry: body.update(train_flops="configs.lm_costs:nope"),
     "no cost function 'configs.lm_costs:nope'"),
])
def test_validate_tells_a_cut_that_is_not_declared_in_full(tmp_path, edit,
                                                           told):
    bench, root = edited_fixture(tmp_path, edit)
    problems = manifest.validate(bench, root)
    if told is None:
        assert problems == []
    else:
        assert any(told in p for p in problems), problems


def test_validate_tells_two_cells_of_one_configuration_and_traffic():
    bad = json.loads(json.dumps(BENCH))
    bad["workloads"].append(dict(bad["workloads"][0], name="big.again"))
    assert any("another cell" in p for p in manifest.validate(bad))


# -- cost functions by module:function ----------------------------------------------

def test_cost_functions_resolve_by_name_and_by_module():
    assert manifest.load_cost("train_step_flops") is \
        kernel_costs.train_step_flops
    assert manifest.load_cost("kernel_costs:packed_attention_train") is \
        kernel_costs.packed_attention_train
    own = manifest.load_cost("configs.lm_costs:train_step_flops", FIXTURE)
    assert own is not kernel_costs.train_step_flops
    dims = manifest.load_config("lm-base-cut", FIXTURE)
    # 6ND of the 2 layers' 6.3M parameters and the tied 16.4M table
    assert 0.9 < own(dims, 1000, 1000, 32, 32) / (6 * 22.7e6 * 1000) < 1.2


@pytest.mark.parametrize("spec", [
    "no_such_function", "kernel_costs:no_such_function", "BF16",
    "configs.no_such_module:f", "configs.lm_costs:train_step_flops",
    "../tests:f", ":"])
def test_an_unknown_cost_function_is_an_error(spec):
    with pytest.raises(manifest.ManifestError):
        manifest.load_cost(spec)          # the fixture's needs its root


def test_the_roofline_reader_takes_the_fixture_s_own_cost():
    spec = manifest.load_layer_metric(OWN_METRIC, FIXTURE)
    dims = manifest.load_config("lm-base-cut", FIXTURE)
    work = [{"rows": 64, "src_width": 32, "trg_width": 32}]
    obs = {"trace": {"window_s": 2.0, "busy_s": 1.5,
                     "kernel_s": {"packed_attention": 4e-4}},
           "traced_work": work, "dims": dims, "root": FIXTURE,
           "peaks": manifest.load_peaks("TPU v5 lite")}
    read = manifest.load_reader(spec["reader"], FIXTURE).read
    flops, nbytes = manifest.load_cost(spec["args"]["cost"], FIXTURE)(
        work, dims)
    assert flops == 2 * 14.0 * 64 * 8 * 32 * 32 * 64
    assert read(obs, spec["args"]) == pytest.approx(
        100.0 * max(flops / 197e12, nbytes / 819e9) / 4e-4)
    with pytest.raises(manifest.ManifestError):
        read(dict(obs, root=None), spec["args"])


# -- what run.py reads from the configuration's file ---------------------------------

@pytest.mark.parametrize("root, bench, cell_name, got", [
    (None, BENCH, "big.train",
     {"dim_emb": 64, "dim_ffn": 128, "heads": 4, "enc_depth": 2,
      "dec_depth": 2, "vocab": 512}),
    (FIXTURE, FIXTURE_BENCH, CELL,
     {"dim_emb": 32, "dim_ffn": 64, "heads": 2, "dec_depth": 2,
      "vocab": 256, "lm": True}),
])
def test_the_built_model_is_held_to_the_file_s_own_keys(root, bench,
                                                        cell_name, got):
    import importlib
    run = importlib.import_module("benchmark.run")
    cell = manifest.Cell(bench, cell_name, root)
    args = types.SimpleNamespace(seed=7, seconds=1, trace=0, rehearse=True)
    ctx = run.Context(cell, args)
    assert ctx.tiny_flags == cell.config["rehearse"]["flags"]
    assert all(ctx.dims[k] == v
               for k, v in cell.config["rehearse"]["dims"].items())
    ctx.check_built(got)
    for key in got:
        with pytest.raises(SystemExit, match="the program built"):
            ctx.check_built(dict(got, **{key: got[key] + 1}))
    # at the real size the file's own sizes stand
    real = run.Context(cell, types.SimpleNamespace(
        seed=7, seconds=1, trace=0, rehearse=False))
    assert real.tiny_flags == [] and real.dims == cell.config


def test_one_stream_is_one_file_of_the_same_lines(tmp_path):
    lines, _ = corpus.make_lines(
        manifest.load_traffic("train-docs", FIXTURE)["lengths"], 50, 256, 7)
    paths = [str(tmp_path / f"c.{i}") for i in range(2)]
    corpus.write_streams(lines, paths[:1])
    assert not os.path.exists(paths[1])
    corpus.write_streams(lines, paths)
    for p in paths:
        with open(p, encoding="utf-8") as fh:
            assert fh.read().split("\n")[:-1] == lines


# -- the rehearsal --------------------------------------------------------------

def test_the_fixture_rehearses_through_the_unchanged_train_driver():
    r = _rehearse(CELL, 1, trace=1, root=FIXTURE)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().split("\n")
    assert lines[-1].startswith("rehearsal complete: correct=True")
    assert not any(l.startswith("{") for l in lines)     # never a result
    assert any(l.startswith("rehearsal_padding_share.train = ")
               for l in lines)
    # no trace of a chip here: the fixture's own roofline has nothing to
    # read and is left out, never 0
    assert not any(OWN_METRIC in l for l in lines)
    assert f"[{CELL}] reference check on a" in r.stderr
