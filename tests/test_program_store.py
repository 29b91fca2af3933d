"""The program store (ISSUE 49): the executables --precompile-buckets
builds are kept under a fingerprint of what shapes them, a warm start
loads them and traces none, and a stale entry cannot run
(marian_tpu/training/program_store.py, graph_group.py::_compile_ahead).
CPU, a one-layer plan over two buckets. The trainer keeps nothing on the
CPU, because XLA:CPU's serialized executables do not all load whole (one
serialized in a process that compiled the same kernel before lacks it:
"Function ... not found" at run time), so the cases switch the store ON
and put an in-memory stand-in under it for `serialize_executable`; ONE
case round-trips for real, in a process of its own."""

import json
import os
import shutil
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from marian_tpu import obs
from marian_tpu.common import profiling, prng
from marian_tpu.common.config_parser import MODE_FLAGS, parse_options
from marian_tpu.models.encoder_decoder import create_model
from marian_tpu.training import program_store
from marian_tpu.training.graph_group import GraphGroup
from marian_tpu.training.program_store import (DENIED_OPTIONS, ProgramStore,
                                               Refused, option_inputs)

from tests.time_limit import time_limit

VOCAB = 96
ARGV = ["--type", "transformer-lm", "--transformer-layer-plan",
        "mla:dense", "--dim-emb", "32", "--transformer-heads", "2",
        "--transformer-dim-ffn", "64", "--plan-mla-dim-nope", "8",
        "--plan-mla-dim-shared", "8", "--plan-mla-dim-v", "8",
        "--plan-mla-latent", "16", "--plan-experts-top-k", "2",
        "--precision", "float32", "float32", "--train-sets", "x",
        "--vocabs", "v", "--length-buckets", "16", "32",
        "--mini-batch-words", "64", "--batch-row-multiple", "1",
        "--learn-rate", "0.01", "--precompile-buckets", "2", "--devices",
        "0"]
WIDTHS = (16, 32, 16)


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs.TRACER.reset()
    yield
    obs.TRACER.reset()


class Counting:
    """The jitted step with its traces and lowerings counted."""

    def __init__(self, fused):
        self._fused, self.traces = fused, 0

    def trace(self, *args, **kw):
        self.traces += 1
        return self._fused.trace(*args, **kw)

    def lower(self, *args, **kw):
        self.traces += 1
        return self._fused.lower(*args, **kw)

    def __getattr__(self, name):
        return getattr(self._fused, name)

    def __call__(self, *args, **kw):
        return self._fused(*args, **kw)


KEPT = {}       # the stand-in's executables, by the bytes it gave for them


def keep_in_memory(exe):
    """`serialize_executable.serialize`'s stand-in: the executable stays
    in this process and the store gets a name for it."""
    payload = b"executable %d of this process" % len(KEPT)
    KEPT[payload] = exe
    return payload, None, None


def take_from_memory(payload, in_tree, out_tree, backend=None,
                     execution_devices=None):
    return KEPT[payload]        # KeyError: nothing this process wrote


def trainer(store_dir, monkeypatch, extra=(), vocab=VOCAB, real=False):
    """A GraphGroup as the trainer builds it, its store at `store_dir`
    (None: the process has no persistent cache), switched on for the CPU;
    unless `real`, the executables stay in memory."""
    from jax.experimental import serialize_executable
    monkeypatch.setattr(profiling, "program_store_dir", lambda: store_dir)
    monkeypatch.setattr(program_store, "OFF_PLATFORMS", ())
    if not real:
        monkeypatch.setattr(serialize_executable, "serialize",
                            keep_in_memory)
        monkeypatch.setattr(serialize_executable, "deserialize_and_load",
                            take_from_memory)
    opts = parse_options(ARGV + list(extra), mode="training")
    model = create_model(opts, vocab, vocab)
    gg = GraphGroup(model, opts)
    key = prng.root_key(5)
    gg.initialize(key, jax.jit(model.init)(key))
    gg._fused = Counting(gg._fused)
    return gg


def batch_of(width, step, tokens=np.uint16, vocab=VOCAB):
    rows = 64 // width
    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(step), (rows, width), 2, vocab))
    lens = np.full((rows,), width - 3, np.int32)
    return {"src_tok": jnp.asarray(ids.astype(tokens)),
            "src_len": jnp.asarray(lens),
            "trg_tok": jnp.asarray(ids.astype(tokens)),
            "trg_len": jnp.asarray(lens)}


def run(gg, widths=WIDTHS, **kw):
    """A few updates; every program compiled ahead waited for. Returns
    the costs."""
    key = prng.root_key(5)
    costs = [float(gg.update(batch_of(w, step, **kw), step, key).loss_sum)
             for step, w in enumerate(widths, 1)]
    for future in gg._ahead.values():
        future.result()
    return costs


def entries(store_dir):
    return sorted(n for n in os.listdir(store_dir) if n.endswith(".exe"))


def sources():
    spans, _ = obs.TRACER.snapshot()
    return [s.attrs for s in spans if s.name == "train.compile_ahead"]


@pytest.fixture(scope="module")
def filled(tmp_path_factory):
    """A store two programs full, written by a first trainer: (its
    directory, that trainer's costs, parameters and program ledger). The
    cases copy it."""
    mp = pytest.MonkeyPatch()
    try:
        store_dir = str(tmp_path_factory.mktemp("store") / "programs")
        gg = trainer(store_dir, mp)
        costs = run(gg)
        assert gg._fused.traces == 2 and len(entries(store_dir)) == 2
        return (store_dir, costs,
                {k: np.asarray(v) for k, v in gg.export_params().items()},
                dict(gg._programs))
    finally:
        mp.undo()


@pytest.fixture
def store_dir(filled, tmp_path):
    """A copy of the filled store that a case may write to."""
    path = str(tmp_path / "programs")
    shutil.copytree(filled[0], path)
    return path


@time_limit(240)
def test_a_warm_start_loads_every_step_and_traces_none(
        filled, store_dir, monkeypatch, tmp_path):
    """The second trainer of a process takes every ahead-compiled step
    from the store, never traces or lowers, and its first three updates
    are bit for bit those of a trainer with the store off; the spans and
    counters say where each program came from."""
    _, _, _, programs = filled
    plain = trainer(None, monkeypatch)
    want = run(plain)
    assert plain._fused.traces == 2         # no store: today's path
    obs.TRACER.enable()
    warm = trainer(store_dir, monkeypatch)
    got = run(warm)
    assert warm._fused.traces == 0
    assert got == want
    for k, v in plain.export_params().items():
        assert np.array_equal(np.asarray(warm.export_params()[k]),
                              np.asarray(v)), k
    # the ledger is filled from a LOADED executable as from a compiled one
    assert sorted(warm._programs) == sorted(programs)
    for key, p in programs.items():
        assert warm._programs[key] == p and p["temp"] > 0 and p["args"] > 0
    attrs = sources()
    assert sorted(a["program"] for a in attrs) == ["2x32", "4x16"]
    for a in attrs:
        assert a["source"] == "store" and a["load_s"] > 0 \
            and a["bytes"] > 0 and a["thread"].startswith("precompile")
        assert "trace_s" not in a and "compile_s" not in a
    assert obs.TRACER.counters() == {
        "startup.store_hits": 2.0, "startup.store_misses": 0.0,
        "startup.store_refused": 0.0}


@time_limit(120)
def test_a_loaded_step_still_donates_its_arguments(store_dir, monkeypatch):
    warm = trainer(store_dir, monkeypatch)
    held = jax.tree_util.tree_leaves((warm.params, warm.opt_state))
    run(warm, widths=(16,))
    assert warm._fused.traces == 0
    assert all(a.is_deleted() for a in held)


def real_round_trip(store_dir):
    """In a process of its own (the case below): a trainer writes its
    steps through the REAL `serialize_executable`, a second one loads
    them, and its three updates are bit for bit a third one's that has
    no store; the loaded steps donate, and say what the compiled said."""
    mp = pytest.MonkeyPatch()
    cold = trainer(store_dir, mp, real=True)
    run(cold)
    assert cold._fused.traces == 2 and len(entries(store_dir)) == 2
    plain = trainer(None, mp, real=True)
    want = run(plain)
    warm = trainer(store_dir, mp, real=True)
    held = jax.tree_util.tree_leaves((warm.params, warm.opt_state))
    got = run(warm)
    assert warm._fused.traces == 0 and got == want, (got, want)
    assert all(a.is_deleted() for a in held)
    for k, v in plain.export_params().items():
        assert np.array_equal(np.asarray(warm.export_params()[k]),
                              np.asarray(v)), k
    assert warm._programs == cold._programs and all(
        p["temp"] > 0 and p["args"] > 0 for p in warm._programs.values())
    print("the round trip is whole")


@time_limit(300)
def test_a_real_round_trip_gives_the_same_updates(tmp_path):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run(
        [sys.executable, "-c", "import sys; from tests import "
         "test_program_store as t; t.real_round_trip(sys.argv[1])",
         str(tmp_path / "programs")], cwd=root, capture_output=True,
        text=True, timeout=280, env=dict(env, JAX_PLATFORMS="cpu"))
    if r.returncode and " not found (type id" in r.stderr:
        pytest.skip("XLA:CPU gave back an executable that lacks a kernel: "
                    "why the trainer keeps nothing on the CPU")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "the round trip is whole" in r.stdout


@time_limit(120)
def test_a_cold_start_says_what_it_traced_and_kept(tmp_path, monkeypatch):
    """A miss compiles as before and writes its entry: the span carries
    the split, the file its readable inputs."""
    obs.TRACER.enable()
    store_dir = str(tmp_path / "programs")
    cold = trainer(store_dir, monkeypatch)
    run(cold)
    assert cold._fused.traces == 2
    for a in sources():
        assert a["source"] == "compiled" and a["bytes"] > 0
        assert min(a["trace_s"], a["lower_s"], a["compile_s"]) > 0
        assert "load_s" not in a
    assert obs.TRACER.counters() == {
        "startup.store_hits": 0.0, "startup.store_misses": 2.0,
        "startup.store_refused": 0.0}
    names = entries(store_dir)
    assert len(names) == 2 and not [
        n for n in os.listdir(store_dir) if n.endswith(".tmp")]
    for name in names:
        with open(os.path.join(store_dir, name), "rb") as fh:
            header = json.loads(fh.readline())
        assert header["fingerprint"] + ".exe" == name
        assert header["name"] in ("4x16", "2x32")
        inputs = header["inputs"]
        assert inputs["options"]["plan-experts-top-k"] == 2
        assert "seed" not in inputs["options"]
        assert inputs["environment"]["jax"] == jax.__version__
        assert inputs["donate"] == [0, 1]
        assert {"params", "opt_state", "batch", "step", "rng"} \
            == set(inputs["args"])


STALE = {
    "top-k": dict(extra=["--plan-experts-top-k", "1"]),
    "precision": dict(extra=["--precision", "bfloat16", "float32"]),
    "checkpointing": dict(extra=["--gradient-checkpointing"]),
    "learn-rate": dict(extra=["--learn-rate", "0.02"]),
    "package": dict(patch=("package_digest", lambda: "another package")),
    "jax": dict(patch=("versions", lambda: {"jax": "0.0.1",
                                            "jaxlib": "0.0.1"})),
    "vocabulary": dict(vocab=104),
    "batch": dict(tokens=np.int32),
}


@time_limit(120)
@pytest.mark.parametrize("kind", sorted(STALE))
def test_a_stale_entry_cannot_run(kind, store_dir, monkeypatch):
    """One case a kind of staleness: every step MISSES, is traced, and is
    kept beside the entries it could not use."""
    case = STALE[kind]
    if "patch" in case:
        monkeypatch.setattr(program_store, *case["patch"])
    vocab = case.get("vocab", VOCAB)
    gg = trainer(store_dir, monkeypatch, case.get("extra", ()), vocab)
    run(gg, tokens=case.get("tokens", np.uint16), vocab=vocab)
    assert gg._fused.traces == 2
    assert len(entries(store_dir)) == 4


@time_limit(120)
@pytest.mark.parametrize("extra", [
    ["--seed", "77"],
    ["--train-sets", "elsewhere/y", "--vocabs", "elsewhere/w", "--model",
     "elsewhere/m.npz", "--valid-sets", "elsewhere/dev"],
    ["--disp-freq", "7", "--quiet", "--log-level", "warn", "--save-freq",
     "3u", "--after-batches", "9"],
], ids=["seed", "paths", "display"])
def test_what_cannot_reach_the_step_still_hits(extra, store_dir,
                                               monkeypatch):
    gg = trainer(store_dir, monkeypatch, extra)
    run(gg)
    assert gg._fused.traces == 0
    assert len(entries(store_dir)) == 2


@time_limit(120)
def test_a_baked_schedule_is_part_of_the_key(store_dir, monkeypatch):
    """What `rebuild` traces into the step beside the options: after a
    decay of the learn rate the programs of the rate before cannot run."""
    gg = trainer(store_dir, monkeypatch)
    gg.schedule.decay_factor = 0.5
    gg.rebuild()
    gg._fused = Counting(gg._fused)
    run(gg)
    assert gg._fused.traces == 2


def test_the_deny_list_is_argued_and_every_other_option_is_in_the_key():
    """The deny-list names only options config_parser has, each with its
    reason, and an option that is NOT on it changes the fingerprint's
    inputs: the walk is over every option, so a new one is in the key
    without an edit."""
    known = {f.name for flags in MODE_FLAGS.values() for f in flags}
    assert set(DENIED_OPTIONS) <= known, set(DENIED_OPTIONS) - known
    assert all(isinstance(why, str) and len(why) > 10
               for why in DENIED_OPTIONS.values())
    base = parse_options(ARGV, mode="training")
    want = option_inputs(base)
    assert not set(want) & set(DENIED_OPTIONS)
    walked = 0
    for f in MODE_FLAGS["training"]:
        changed = base.clone()
        changed.set(f.name, ["another value", base.get(f.name, None)])
        assert (option_inputs(changed) == want) == (f.name in DENIED_OPTIONS), \
            f.name
        walked += 1
    assert walked > 300


def test_the_fingerprint_follows_its_inputs(monkeypatch, tmp_path):
    """The options, the baked values, the arguments and the donation each
    move the fingerprint; equal inputs give it again."""
    from marian_tpu.parallel import mesh as M
    opts = parse_options(ARGV, mode="training")
    mesh = M.make_mesh(opts)
    arg = {"batch": [["['x']", [4, 16], "uint16", "None"]]}

    def mark(options=opts, baked=None, args=arg, donate=(0, 1)):
        store = ProgramStore(str(tmp_path), mesh, options,
                             baked or {"schedule": {"decay_factor": 1.0}})
        return store.fingerprint(args, donate)[0]
    marks = {
        mark(),
        mark(options=opts.with_({"plan-experts-top-k": 1})),
        mark(baked={"schedule": {"decay_factor": 0.5}}),
        mark(args={"batch": [["['x']", [2, 32], "uint16", "None"]]}),
        mark(args={"batch": [["['x']", [4, 16], "uint16", "replicated"]]}),
        mark(donate=())}
    assert len(marks) == 6
    assert mark() == mark(options=opts.with_({"seed": 9, "model": "m2"}))
    monkeypatch.setenv("MARIAN_FLASH_BLOCK_Q", "256")
    assert mark() not in marks              # tile sizes shape the kernels
    monkeypatch.delenv("MARIAN_FLASH_BLOCK_Q")
    monkeypatch.setenv("MARIAN_TRACE", "1")
    assert mark() in marks                  # watching a start costs none


def damage_truncated(store_dir):
    for name in entries(store_dir):
        path = os.path.join(store_dir, name)
        with open(path, "r+b") as fh:
            fh.truncate(os.path.getsize(path) - 100)


def damage_swapped(store_dir):
    a, b = (os.path.join(store_dir, n) for n in entries(store_dir))
    os.rename(a, a + ".was")
    os.rename(b, a)
    os.rename(a + ".was", b)


def damage_garbage(store_dir):
    for name in entries(store_dir):
        with open(os.path.join(store_dir, name), "wb") as fh:
            fh.write(b"not a step program\n")


def damage_payload(store_dir):
    """Whole by its header, and nothing that unpacks to a program."""
    for name in entries(store_dir):
        path = os.path.join(store_dir, name)
        with open(path, "rb") as fh:
            line = fh.readline()
        size = os.path.getsize(path)
        with open(path, "r+b") as fh:
            fh.seek(len(line))
            fh.write(b"\0" * (size - len(line)))


@time_limit(240)
@pytest.mark.parametrize("damage", [damage_truncated, damage_swapped,
                                    damage_garbage, damage_payload],
                         ids=lambda f: f.__name__[7:])
def test_a_damaged_entry_is_refused_compiled_over_and_replaced(
        damage, store_dir, monkeypatch, filled):
    damage(store_dir)
    obs.TRACER.enable()
    gg = trainer(store_dir, monkeypatch)
    assert run(gg) == filled[1]
    assert gg._fused.traces == 2
    assert obs.TRACER.counters() == {
        "startup.store_hits": 0.0, "startup.store_misses": 2.0,
        "startup.store_refused": 2.0}
    assert len(entries(store_dir)) == 2
    obs.TRACER.reset()
    again = trainer(store_dir, monkeypatch)     # the entries are whole
    assert run(again) == filled[1] and again._fused.traces == 0


@time_limit(120)
def test_two_writers_of_one_entry_leave_one_whole_file(store_dir,
                                                       monkeypatch):
    """Sixteen threads write ONE entry at once while others read it: a
    reader gets a whole entry or none, and one whole file is left."""
    gg = trainer(store_dir, monkeypatch)
    run(gg, widths=(16,))
    store = gg._program_store()
    name, = [n for n in entries(store_dir)
             if json.loads(open(os.path.join(store_dir, n), "rb")
                           .readline())["name"] == "4x16"]
    with open(os.path.join(store_dir, name), "rb") as fh:
        inputs = json.loads(fh.readline())["inputs"]
    mark = name[:-len(".exe")]
    exe = store.load(mark, inputs).exe
    costs = next({k: v for k, v in p.items() if k != "name"}
                 for p in gg._programs.values() if p["name"] == "4x16")
    faults, was = [], sys.getswitchinterval()

    def write():
        if not store.save(mark, inputs, "4x16", exe, "module {}", costs):
            faults.append("a writer wrote nothing")

    def read():
        try:
            if store.load(mark, inputs) is None:
                faults.append("a reader found no entry")
        except Refused as e:
            faults.append(f"a reader was refused: {e}")
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=write if i % 4 else read)
                   for i in range(20)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(was)
    assert not [t for t in threads if t.is_alive()] and not faults, faults
    assert sorted(os.listdir(store_dir)) == entries(store_dir)
    assert len(entries(store_dir)) == 2
    assert store.load(mark, inputs).costs == costs


@time_limit(120)
def test_an_entry_is_packed_with_what_is_installed(store_dir, monkeypatch):
    """zstd where the process has it, zlib where not; an entry packed
    with what the reader lacks is refused, compiled over and replaced."""
    def codecs():
        return {json.loads(open(os.path.join(store_dir, n), "rb")
                           .readline())["codec"] for n in entries(store_dir)}
    was = codecs()
    assert was == {"zstd" if program_store.zstandard else "zlib"}
    monkeypatch.setattr(program_store, "zstandard", None)
    obs.TRACER.enable()
    gg = trainer(store_dir, monkeypatch)
    run(gg)
    unreadable = 2 if was == {"zstd"} else 0
    assert gg._fused.traces == unreadable
    assert obs.TRACER.counters()["startup.store_refused"] == unreadable
    assert codecs() == {"zlib"}
    again = trainer(store_dir, monkeypatch)
    run(again)
    assert again._fused.traces == 0


@time_limit(120)
def test_a_loaded_programs_text_goes_where_the_dump_goes(
        store_dir, monkeypatch, tmp_path):
    """JAX_DUMP_IR_TO holds the text of every program that runs (the
    benchmark reads the kernels of the compiled step from it): a program
    that was loaded, and so never handed to the compiler, writes its
    own."""
    dump = str(tmp_path / "ir")
    was = jax.config.read("jax_dump_ir_to")
    jax.config.update("jax_dump_ir_to", dump)
    try:
        gg = trainer(store_dir, monkeypatch)
        run(gg)
    finally:
        jax.config.update("jax_dump_ir_to", was)
    assert gg._fused.traces == 0
    stored = sorted(n for n in os.listdir(dump)
                    if n.startswith("jax_ir_stored_"))
    assert [n.split("_")[3] for n in stored] == ["2x32", "4x16"]
    for name in stored:
        text = open(os.path.join(dump, name)).read()
        assert "module @jit_" in text and "stablehlo" in text


def test_the_cpus_programs_are_not_kept(tmp_path, monkeypatch):
    monkeypatch.setattr(profiling, "program_store_dir",
                        lambda: str(tmp_path / "programs"))
    opts = parse_options(ARGV, mode="training")
    gg = GraphGroup(create_model(opts, VOCAB, VOCAB), opts)
    assert jax.devices()[0].platform == "cpu"
    assert gg._program_store() is None


def test_the_store_lives_beside_the_persistent_cache(tmp_path):
    """On where the compilation cache is on, and nowhere else: no flag."""
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_enable_compilation_cache)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert profiling.program_store_dir() is None
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert profiling.program_store_dir() == str(tmp_path / "programs")
        jax.config.update("jax_enable_compilation_cache", False)
        assert profiling.program_store_dir() is None
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_enable_compilation_cache", was[1])
