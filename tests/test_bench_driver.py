"""bench.py driver-contract tests: one process, a chip or a failure. On the
CPU backend the benchmark refuses to measure — no metric line, a clear
message, a non-zero exit — in both of its modes.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_peak_flops_lookup():
    bench = _load_bench()
    assert bench._peak_flops("TPU v5 lite") == 197e12
    assert bench._peak_flops("TPU v5p") == 459e12
    assert bench._peak_flops("TPU v4") == 275e12
    assert bench._peak_flops("unknown accelerator") is None
    assert bench._peak_flops(None) is None


@pytest.mark.parametrize("mode", [[], ["--multichip"]],
                         ids=["train", "multichip"])
def test_bench_refuses_the_cpu_backend(mode, tmp_path):
    """No cached row, no CPU fallback, no child: with no accelerator the
    run exits non-zero, says why, and prints nothing a reader could take
    for a measurement."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")] + mode,
                       capture_output=True, text=True, env=env, timeout=240)
    assert r.returncode != 0, r.stdout + r.stderr
    assert "no accelerator found" in r.stderr
    assert "refusing to measure on the host CPU" in r.stderr
    assert '"metric"' not in r.stdout and r.stdout.strip() == ""


def test_bench_has_no_second_process():
    """The parent orchestrator is gone: nothing in bench.py starts a
    process, reads a cached row or reaches for a fallback."""
    with open(os.path.join(ROOT, "bench.py")) as f:
        src = f.read()
    for gone in ("subprocess", "Popen", "_read_cache", "_write_cache",
                 "BENCH_FORCE_CPU", "BENCH_SKIP_TPU", "aot_load",
                 "degraded", "preflight"):
        assert gone not in src, gone
    for name in ("bench_cache.json", "VERDICT.md",
                 os.path.join("tools", "aot_warm.py"),
                 os.path.join("tools", "perf_results")):
        assert not os.path.exists(os.path.join(ROOT, name)), name


def test_peak_flops_shares_xcost_table():
    """bench's per-chip peaks come from the perf layer's single source of
    truth (observability/xcost.py)."""
    bench = _load_bench()
    from mxnet_tpu.observability import xcost
    for kind in ("TPU v5 lite", "TPU v5p", "TPU v4", "TPU v3"):
        assert bench._peak_flops(kind) == xcost.peak_flops(kind)
