"""Smoke tests for the driver-facing bench entry points (bench.py /
bench_decode.py). These are the round's headline deliverable — a
regression here would otherwise surface only when the driver runs the
bench on scarce TPU time."""

import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, env_extra, tmp_path, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    r = subprocess.run([sys.executable, os.path.join(ROOT, script)],
                      capture_output=True, text=True, env=env,
                      timeout=timeout, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    return json.loads(line)


def test_train_bench_tiny_contract(tmp_path):
    out = _run("bench.py", {"MARIAN_BENCH_PRESET": "tiny"}, tmp_path)
    # metric/value/unit on ONE line; a CPU smoke never carries the device
    # metric's name, a baseline ratio or an MFU
    assert out["metric"] == "cpu_smoke_src_tokens_per_sec"
    assert out["value"] > 0 and out["unit"] == "src-tokens/sec/chip"
    assert out["vs_baseline"] is None and out["mfu"] is None
    assert out["chip"] == "cpu" and out["platform"] == "cpu"
    assert out["flops_per_src_token"] > 0


def test_decode_bench_tiny_contract(tmp_path):
    out = _run("bench_decode.py", {"MARIAN_DECBENCH_PRESET": "tiny"},
               tmp_path)
    assert out["metric"] == "cpu_smoke_beam6_sentences_per_sec"
    assert out["value"] > 0 and out["unit"] == "sent/sec"
    assert out["platform"] == "cpu"


@pytest.mark.parametrize("script,env", [
    ("bench.py", {"MARIAN_BENCH_PRESET": "big"}),
    ("bench_decode.py", {"MARIAN_DECBENCH_PRESET": "big"}),
])
def test_no_chip_fails_instead_of_falling_back(script, env):
    """The real presets need a TPU: on a CPU they exit non-zero and print
    no row (no fallback, no stale replay)."""
    r = subprocess.run([sys.executable, os.path.join(ROOT, script)],
                       capture_output=True, text=True, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu", **env),
                       timeout=120)
    assert r.returncode != 0
    assert "not a device metric" in r.stderr
    assert not [l for l in r.stdout.splitlines() if l.startswith("{")]
