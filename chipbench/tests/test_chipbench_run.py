"""A whole run of a tiny cell on the CPU (the look for a chip skipped):
the result line's schema, and that the run is judged correct; and the
real entry point refusing to run without a TPU or without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import tinycell

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("config", ["qwen2-0.5b", "mamba2-780m"])
def test_tiny_run_prints_the_contract_line(monkeypatch, config):
    out = tinycell.run_tiny(monkeypatch, config)
    line = json.loads(json.dumps(out))
    assert list(line)[-1] == "checks"
    assert set(RESULT_KEYS) <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "step_ms.p90",
                                    "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
        assert c["value"] <= c["limit"]


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-0.5b.local-tokens", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_fails_without_a_result():
    p = _run_entry(tinycell.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_fail_without_a_result(tmp_path):
    shutil.copy(os.path.join(tinycell.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tinycell.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_entry(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
