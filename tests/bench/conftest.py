"""Fixtures of the benchmark's tests: a temporary checkout holding the
benchmark's definition files, and a CPU run of ``bench/run.py``."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

ROOT = Path(__file__).resolve().parents[2]


def copy_definition(dst: Path) -> Path:
    """Copies ``BENCHMARK.json`` and the configuration, traffic, operation
    and metric files into the checkout ``dst``."""
    shutil.copy(ROOT / "BENCHMARK.json", dst)
    for d in ("configs", "traffic", "ops", "checks", "metrics"):
        shutil.copytree(ROOT / "bench" / d, dst / "bench" / d)
    return dst


@pytest.fixture
def bench_root(tmp_path) -> Path:
    """The benchmark's definition files in a temporary checkout that a test
    may edit."""
    return copy_definition(tmp_path)


@pytest.fixture
def run_cpu(monkeypatch, capsys):
    """``run_cpu(root, workload, seconds=..., trace=...)`` runs the harness's
    ``main`` in this process on the CPU, the TPU check steered past here and
    nowhere else, and returns (result, stdout, stderr).  JAX's compilation
    cache settings, which a run changes, are put back afterwards."""
    from bench import run
    monkeypatch.setattr(run, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}

    def call(root: Path, workload: str, seed: int = 2**31 + 7,
             seconds: float = 1, trace: int = 0):
        capsys.readouterr()
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root)
        out, err = capsys.readouterr()
        assert rc == 0, err
        return json.loads(out.strip().splitlines()[-1]), out, err

    yield call
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()

