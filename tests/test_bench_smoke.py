"""Smoke tests for bench_decode.py, the decode bench that stays until the
benchmark holds a decode cell (ROADMAP B3): a regression here would
otherwise surface only on scarce TPU time."""

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


def test_decode_bench_tiny_contract(tmp_path):
    out = _run("bench_decode.py", {"MARIAN_DECBENCH_PRESET": "tiny"},
               tmp_path)
    assert out["metric"] == "cpu_smoke_beam6_sentences_per_sec"
    assert out["value"] > 0 and out["unit"] == "sent/sec"
    assert out["platform"] == "cpu"


@pytest.mark.parametrize("script,env", [
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
