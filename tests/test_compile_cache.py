"""Persisted XLA compile cache as a bundle member
(marian_tpu/serving/lifecycle/compile_cache.py — ISSUE 20 tentpole):
where the process's ONE cache directory comes from
(common/profiling.py), key derivation + strict matching, pack/adopt
roundtrip with the event ledger, refusal paths (key mismatch, path
traversal, missing member), and THE acceptance: a cache-backed swap
warmup keeps the marian_compile_backend_seconds_total
{trigger=swap-warmup} ledger ~flat and leaves a jitwit-strict window
with zero post-warm compiles (no wall-clock assertion: a CPU timing says
nothing about the chip).

All on CPU: jax's persistent cache content-addresses CPU executables
exactly like TPU ones; `place_cache` zeroes jax's persistence
thresholds so the tiny tier-1 programs persist too.
"""

import json
import os
import zipfile

import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache.compilation_cache import reset_cache

from marian_tpu import obs
from marian_tpu.common import jitwit
from marian_tpu.common.profiling import (CACHE_DIR_ENV,
                                         compilation_cache_dir,
                                         enable_compilation_cache)
from marian_tpu.serving import metrics as msm
from marian_tpu.serving.lifecycle import compile_cache as cc
from marian_tpu.serving.lifecycle.warmup import warm_executor
from marian_tpu.training import bundle as bdl


@pytest.fixture(autouse=True)
def _restore_cache_config():
    """Every test starts cache-disabled and leaves the process as it
    found it: jax's persistent cache config restored and the memoized
    cache instance dropped — so no later suite silently writes
    executables into a deleted tmp dir."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_enable_xla_caches")
    saved = {k: jax.config._read(k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", None)
    reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    reset_cache()


def place_cache(monkeypatch, path):
    """What a deployment does from outside: name the directory in
    $JAX_COMPILATION_CACHE_DIR. JAX reads that variable at import; a test
    process is past that point, so the read is replayed here (with the
    persistence thresholds zeroed for CPU-sized programs). The program's
    own enable call must then agree with it and set nothing."""
    monkeypatch.setenv(CACHE_DIR_ENV, str(path))
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    reset_cache()
    assert enable_compilation_cache() == str(path)
    assert cc.active_dir() == str(path)
    return path


def write_tiny_bundle(model_path, extra_members=None):
    def w(p):
        with open(p, "w", encoding="utf-8") as fh:
            fh.write("m")
    members = {"m.npz": w}
    members.update(extra_members or {})
    return bdl.write_bundle(str(model_path), members)


def heavy_factory(bundle_dir, manifest):
    """An executor whose first translate pays a REAL compile (30 fused
    tanh/matmul iterations — ~0.5s of XLA work on CPU): jit-on-first-
    call, so the compile lands inside warmup's golden smoke under the
    swap-warmup trigger, exactly like a real model's serving buckets."""
    def _body(x):
        for _ in range(40):
            x = jnp.tanh(x @ x.T) @ x
        return x
    jf = jax.jit(_body)
    x = jnp.ones((96, 96), jnp.float32)

    def translate(lines):
        jf(x).block_until_ready()
        return list(lines)
    return translate


def events():
    e = cc._events()
    return {k: e.labels(k).value for k in
            ("packed", "adopted", "miss", "key-mismatch", "error")}


# ---------------------------------------------------------------------------
# one cache directory, placed from outside
# ---------------------------------------------------------------------------

class TestCacheDir:
    def test_env_var_set_code_sets_no_directory(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path / "outside"))
        assert compilation_cache_dir() == str(tmp_path / "outside")
        assert enable_compilation_cache() == str(tmp_path / "outside")
        # the directory is JAX's own reading of the variable: the
        # program made no jax_compilation_cache_dir update of its own
        assert jax.config.jax_compilation_cache_dir is None
        assert (tmp_path / "outside").is_dir()

    def test_unset_is_the_fixed_path_under_the_checkout(self, monkeypatch):
        monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(root, ".cache", "xla")
        assert compilation_cache_dir() == want
        assert enable_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert cc.active_dir() == want
        assert enable_compilation_cache() == want      # idempotent

    def test_entry_points_all_enable_it(self):
        """marian-train, marian-decoder and marian-server: one call each,
        and nothing else in the package sets a cache directory."""
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        callers, setters = [], []
        for dirpath, _dirs, files in os.walk(os.path.join(root,
                                                          "marian_tpu")):
            for name in files:
                if not name.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, name), root)
                text = open(os.path.join(dirpath, name)).read()
                if "enable_compilation_cache()" in text:
                    callers.append(rel)
                if '"jax_compilation_cache_dir"' in text:
                    setters.append(rel)
        assert {"marian_tpu/training/train.py",
                "marian_tpu/translator/translator.py",
                "marian_tpu/server/server.py"} <= set(callers)
        assert setters == ["marian_tpu/common/profiling.py"]


# ---------------------------------------------------------------------------
# cache key derivation + matching
# ---------------------------------------------------------------------------

class TestCacheKey:
    def test_key_fields(self):
        key = cc.cache_key("deadbeef")
        assert key is not None
        for field in ("chip", "platform", "n_devices", "jax",
                      "flags_sha", "compat"):
            assert key[field], field
        assert key["platform"] == "cpu"
        assert key["compat"] == "deadbeef"

    def test_key_matches_strict_fields(self):
        key = cc.cache_key("")
        ok, why = cc.key_matches(dict(key), key)
        assert ok and not why
        # a cache built for different silicon must never be adopted
        for field in ("chip", "platform", "n_devices", "jax",
                      "flags_sha"):
            bad = dict(key)
            bad[field] = "tpu-v99"
            ok, why = cc.key_matches(bad, key)
            assert not ok and field in why

    def test_compat_compared_only_when_both_recorded(self):
        key = cc.cache_key("aaa")
        # v1 manifests carry no compat: permissive, like bundle compat_ok
        assert cc.key_matches(dict(key, compat=""), key)[0]
        assert cc.key_matches(key, dict(key, compat=""))[0]
        ok, why = cc.key_matches(dict(key, compat="bbb"), key)
        assert not ok and "compat" in why


# ---------------------------------------------------------------------------
# pack / adopt roundtrip + refusal paths (the event ledger)
# ---------------------------------------------------------------------------

class TestPackAdopt:
    def test_pack_without_enable_raises(self, tmp_path):
        writer = cc.pack_member()
        with pytest.raises(RuntimeError, match="no persistent cache"):
            writer(str(tmp_path / "xla_cache.zip"))

    def test_roundtrip(self, tmp_path, monkeypatch):
        src = place_cache(monkeypatch, tmp_path / "cache-src")
        (src / "sub").mkdir()
        (src / "sub" / "entry-1").write_text("compiled bits")
        before = events()
        bdir = write_tiny_bundle(
            tmp_path / "m.npz", {cc.CACHE_MEMBER: cc.pack_member()})
        assert events()["packed"] == before["packed"] + 1
        with zipfile.ZipFile(os.path.join(bdir, cc.CACHE_MEMBER)) as zf:
            names = set(zf.namelist())
        assert cc.KEY_FILE in names and "sub/entry-1" in names
        # another process, its cache placed elsewhere: adopt from the bundle
        live = place_cache(monkeypatch, tmp_path / "elsewhere")
        adopted, dest = cc.adopt(bdir)
        assert adopted and dest == str(live)
        assert (live / "sub" / "entry-1").read_text() == "compiled bits"
        assert events()["adopted"] == before["adopted"] + 1

    def test_adopt_unpacks_into_the_active_directory(self, tmp_path,
                                                     monkeypatch):
        """No second directory, no switch: a bundle's entries land
        beside what the process has already compiled, and the cache
        keeps writing where it was placed."""
        src = place_cache(monkeypatch, tmp_path / "producer")
        (src / "entry-a").write_text("a")
        bdir = write_tiny_bundle(
            tmp_path / "m.npz", {cc.CACHE_MEMBER: cc.pack_member()})
        live = place_cache(monkeypatch, tmp_path / "live")
        (live / "entry-b").write_text("b")
        made_before = set(os.listdir(tmp_path))
        adopted, dest = cc.adopt(bdir)
        assert adopted and dest == str(live)
        assert cc.active_dir() == str(live)
        assert (live / "entry-a").exists() and (live / "entry-b").exists()
        assert set(os.listdir(tmp_path)) == made_before

    def test_missing_member_is_a_counted_miss(self, tmp_path):
        bdir = write_tiny_bundle(tmp_path / "m.npz")
        before = events()
        adopted, why = cc.adopt(bdir)
        assert not adopted and "no compile-cache member" in why
        assert events()["miss"] == before["miss"] + 1

    def test_key_mismatch_refused(self, tmp_path):
        """A cache recorded on different silicon is never installed —
        the refusal is visible in the ledger, not a silent jax re-key."""
        bdir = tmp_path / "bundle"
        bdir.mkdir()
        key = cc.cache_key("")
        key["chip"] = "tpu-v99"
        with zipfile.ZipFile(bdir / cc.CACHE_MEMBER, "w") as zf:
            zf.writestr(cc.KEY_FILE, json.dumps(key))
            zf.writestr("entry-1", "alien bits")
        before = events()
        adopted, why = cc.adopt(str(bdir))
        assert not adopted and "chip mismatch" in why
        assert events()["key-mismatch"] == before["key-mismatch"] + 1
        assert cc.active_dir() is None      # refused before any enabling

    def test_member_without_key_record_is_an_error(self, tmp_path):
        bdir = tmp_path / "bundle"
        bdir.mkdir()
        with zipfile.ZipFile(bdir / cc.CACHE_MEMBER, "w") as zf:
            zf.writestr("entry-1", "bits")
        before = events()
        adopted, why = cc.adopt(str(bdir))
        assert not adopted and cc.KEY_FILE in why
        assert events()["error"] == before["error"] + 1

    def test_path_traversal_member_refused(self, tmp_path, monkeypatch):
        place_cache(monkeypatch, tmp_path / "live")
        bdir = tmp_path / "bundle"
        bdir.mkdir()
        with zipfile.ZipFile(bdir / cc.CACHE_MEMBER, "w") as zf:
            zf.writestr(cc.KEY_FILE, json.dumps(cc.cache_key("")))
            zf.writestr("../evil", "escape")
        before = events()
        adopted, why = cc.adopt(str(bdir))
        assert not adopted and "escapes" in why
        assert events()["error"] == before["error"] + 1
        assert not (tmp_path / "evil").exists()


# ---------------------------------------------------------------------------
# THE acceptance: cache-backed swap warmup is load+verify, not full jit
# ---------------------------------------------------------------------------

class TestCachedWarmup:
    def test_cached_swap_warmup_ledger_stays_flat(self, tmp_path,
                                                  monkeypatch):
        """Cold warmup pays the full jit; with a bundle carrying the
        packed cache the swap-warmup compile ledger
        (marian_compile_backend_seconds_total{trigger=swap-warmup})
        stays ~flat, and a jitwit strict window over post-warm traffic
        sees zero compiles (ISSUE 20 acceptance). What that is worth in
        seconds is a question for the chip, not for this CPU."""
        reg = msm.Registry()
        obs.PERF.enable(reg)

        def warm(model_path):
            bundle_dir, manifest = bdl.latest_valid_bundle(
                str(model_path))
            return warm_executor(bundle_dir, manifest, heavy_factory,
                                 golden=["g"])

        def ledger():
            return obs.PERF.m_backend_s.labels("swap-warmup").value

        # -- cold: no cache member; the live dir persists the compile
        place_cache(monkeypatch, tmp_path / "live-cache")
        write_tiny_bundle(tmp_path / "m1.npz")
        warm(tmp_path / "m1.npz")
        ledger_cold = ledger()
        assert ledger_cold > 0          # the compile was attributed

        # -- pack the now-populated cache into the NEXT bundle
        write_tiny_bundle(
            tmp_path / "m2.npz", {cc.CACHE_MEMBER: cc.pack_member()})

        # -- fresh-process shape: executables dropped, an empty cache
        # placed elsewhere — only the bundle's entries can hit
        jax.clear_caches()
        place_cache(monkeypatch, tmp_path / "fresh-cache")
        ex2 = warm(tmp_path / "m2.npz")
        ledger_warm = ledger() - ledger_cold

        assert ledger_warm < ledger_cold / 5, \
            f"swap-warmup compile ledger not ~flat across the " \
            f"cache-backed swap: cold {ledger_cold:.3f}s vs warm " \
            f"{ledger_warm:.3f}s"
        # post-warm traffic retraces nothing: the strict-window contract
        with jitwit.strict() as w:
            assert ex2(["a", "b"]) == ["a", "b"]
        assert w.compiles == []

    def test_event_series_registered(self):
        """marian_compile_cache_events_total is the series the fleet
        runbook pages on — a rename breaks this census first."""
        e = cc._events()
        e.labels("adopted").inc(0)
        assert "marian_compile_cache_events_total" \
            in msm.REGISTRY.render()
