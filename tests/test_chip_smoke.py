"""CPU guards for ``chip_smoke.py``: its phases at a small size against
numpy, its refusal to run without a TPU, and where the compile cache goes.

Phase (a) runs at full size here: it is small, and its modelled statistics
must equal the values the script holds the chip to.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax

import chip_smoke
from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_phase_ddt_recv_matches_golden():
    res = chip_smoke.phase_ddt_recv()
    assert res["modelled"] == chip_smoke.GOLDEN["ddt_recv"]
    assert res["timing"]["wall_s"] >= res["timing"]["first_tick_s"] > 0


def test_phase_allreduce_small():
    res = chip_smoke.phase_allreduce(n_ranks=4, nbytes=96 << 10)
    m = res["modelled"]
    assert m["algorithm"] == "allreduce_rab"
    assert m["ticks"] > 0 and m["bytes_wire"] > 0 and m["msgs_total"] > 0
    assert res["devices"] == {jax.devices()[0]}


def test_phase_grad_sync_small():
    res = chip_smoke.phase_grad_sync(n_ranks=2,
                                     shapes=dict(w=(64, 32), b=(32,)))
    m = res["modelled"]
    assert m["ticks"] > 0 and m["frames_lost"] == 0


def test_phase_kernels_small_interpreted_on_cpu():
    res = chip_smoke.phase_kernels(batch=8, n_frames=8, count=4)
    # on the CPU every kernel runs in interpret mode: no Mosaic call
    assert res["compiled"] == dict(matcher=False, checksum=False,
                                   ddt_u8=False, ddt_f32=False)


def test_script_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.configure() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_follows_the_environment(tmp_path):
    code = ("import json, jax, jax.numpy as jnp\n"
            "from repro import compile_cache\n"
            "d = compile_cache.configure()\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()\n"
            "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr
    used, config = json.loads(proc.stdout.strip().splitlines()[-1])
    assert used == config == str(tmp_path)
    assert any(tmp_path.iterdir())

