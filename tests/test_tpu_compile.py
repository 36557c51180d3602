"""Ahead-of-time compiles for a described TPU v5e, with no chip attached.

The chip's own compiler runs here against a described ``v5e:2x2`` topology
and refuses what Mosaic or XLA:TPU would refuse on the chip: the NIC's
Pallas kernels at the NIC's shapes (each must lower to a Mosaic
``tpu_custom_call``), one ``SpinNIC`` step at the large MPI configuration,
and the fabric's batched link pop/push over 8 links.  Nothing runs, so
this says nothing about results or speed.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and test workers import every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.bench_mpi import large_cfg
from repro import kernels, mpi
from repro.core import packet as pkt
from repro.kernels.checksum import ops as checksum_ops
from repro.kernels.ddt import ops as ddt_ops
from repro.kernels.matcher import ops as matcher_ops
from repro.net import LinkConfig
from repro.net import fabric as fabriclib
from repro.net import link as linklib


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # an AOT compile cannot be read back without a chip: keep it out of
    # the persistent cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Steer the ops wrappers to the Mosaic path: the default backend here
    is the CPU, where they would pick interpret mode."""
    monkeypatch.setattr(kernels, "interpret", lambda: False)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel", ["matcher", "checksum", "ddt_u8",
                                    "ddt_f32"])
def test_nic_kernel_compiles_to_mosaic(one_chip, compiled_kernels, kernel):
    if kernel == "matcher":
        rules = jnp.zeros((2, 4, 4), jnp.uint32)
        modes = jnp.zeros((2,), jnp.int32)
        exe = _compile(
            lambda w: matcher_ops.match(w, rules, modes, use_kernel=True),
            _spec(one_chip, (128, pkt.WORDS), jnp.uint32))
    elif kernel == "checksum":
        exe = _compile(
            lambda d, n: checksum_ops.internet_checksum(
                d, n, start=pkt.L4_BASE, use_kernel=True),
            _spec(one_chip, (128, pkt.MTU), jnp.uint8),
            _spec(one_chip, (128,), jnp.int32))
    else:
        dtype = jnp.uint8 if kernel == "ddt_u8" else jnp.float32
        exe = _compile(lambda s, i: ddt_ops.gather(s, i, use_kernel=True),
                       _spec(one_chip, (1 << 16,), dtype),
                       _spec(one_chip, (1 << 16,), jnp.int32))
    assert "tpu_custom_call" in exe.as_text()


def test_spin_nic_step_compiles_at_large_cfg(one_chip):
    comm = mpi.Communicator(8, cfg=large_cfg())
    nic = comm.nic
    state = _on(one_chip, jax.eval_shape(nic.init_state))
    batch = _on(one_chip, jax.eval_shape(lambda: pkt.PacketBatch.empty(
        nic.batch)))
    exe = _compile(nic._step_impl, state, batch)
    mem = exe.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 64 << 20


def test_fabric_pop_push_compile_for_8_links(one_chip):
    cfg = LinkConfig(loss=0.02, latency=1)
    n_links, batch, p = 8, large_cfg().batch, 32
    stack = _on(one_chip, jax.eval_shape(lambda: jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[linklib.make_state(cfg.capacity) for _ in range(n_links)])))
    now = _spec(one_chip, (), jnp.int32)
    fabriclib._pop_all.lower(batch, stack, now).compile()
    keys = _spec(one_chip, (n_links, 2), jnp.uint32)
    egress = pkt.PacketBatch(_spec(one_chip, (n_links, p, pkt.MTU), jnp.uint8),
                             _spec(one_chip, (n_links, p), jnp.int32),
                             _spec(one_chip, (n_links, p), jnp.bool_))
    fabriclib._push_all.lower(cfg, stack, keys, egress, now).compile()
