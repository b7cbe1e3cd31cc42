"""Compiles for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed beside JAX, so the main-path program and
the Pallas kernels compile here for a ``v5e:2x2`` topology that is
described, not attached.  What the chip's compiler refuses — a program
that does not fit the device, a kernel it cannot lower — fails here at
no chip time.  Nothing runs, so these say nothing about results or
times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and every test
worker imports this file.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (CostConfig, TraceSpec, benchmark_machine, bhi,
                        bhi_mig, bind_all, linux_default, sweep_lanes)
from repro.kernels.block_copy import block_copy_kernel
from repro.kernels.paged_attention import paged_attention_kernel

sweep_mod = importlib.import_module("repro.core.sweep")

DEVICE_BYTES = 16 << 30           # HBM of one v5e chip

PAGED_ATTENTION_SHAPES = [(1, 1, 1, 128, 8, 8, 2), (2, 2, 4, 128, 16, 16, 4),
                          (3, 4, 2, 256, 32, 8, 5), (2, 2, 8, 128, 16, 32, 3)]
BLOCK_COPY_SHAPES = [(8, 8, 1, 128, 1), (16, 16, 2, 128, 5),
                     (32, 8, 4, 256, 12)]


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 topology, with JAX's persistent compilation
    cache off around its compiles (a compile for a described chip can be
    written to the cache but never read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described here: "
                            f"{e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def on(sharding, tree):
    """Shapes of ``tree`` placed on the described device."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


class _Captured(Exception):
    pass


def test_main_path_fits_one_chip(one_chip, monkeypatch):
    """The ``sweep_lanes`` runner at benchmark-machine size, 4 lanes of a
    short memcached trace: arguments, outputs and temporaries stay within
    one chip's 16 GiB.  The inputs are built on the host by
    ``sweep_lanes`` itself, captured before they would run, and handed
    to the runner as shapes on the described device."""
    captured = {}
    real_runner = sweep_mod._sweep_runner

    def capture(*a, **kw):
        runner = real_runner(*a, **kw)

        def stop(*args):
            captured["runner"], captured["args"] = runner, args
            raise _Captured
        return stop

    monkeypatch.setattr(sweep_mod, "_sweep_runner", capture)
    mc = benchmark_machine()
    tr = TraceSpec(workload="memcached", footprint=1 << 14,
                   run_steps=512).build(mc)
    pcs = [linux_default(), bhi(), bhi_mig(), bind_all()]
    with pytest.raises(_Captured):
        sweep_lanes(mc, [CostConfig()] * 4, pcs, [tr] * 4,
                    group=mc.n_threads)
    compiled = captured["runner"].lower(
        *on(one_chip, captured["args"])).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < DEVICE_BYTES, mem


@pytest.mark.parametrize("B,KH,G,Dh,P,bs,NB", PAGED_ATTENTION_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles(one_chip, B, KH, G, Dh, P, bs, NB, dtype):
    args = (jax.ShapeDtypeStruct((B, KH, G, Dh), dtype),
            jax.ShapeDtypeStruct((KH, P, bs, Dh), dtype),
            jax.ShapeDtypeStruct((KH, P, bs, Dh), dtype),
            jax.ShapeDtypeStruct((B, NB), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32))
    compiled = jax.jit(paged_attention_kernel).lower(
        *on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("P,bs,KH,Dh,M", BLOCK_COPY_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_copy_compiles(one_chip, P, bs, KH, Dh, M, dtype):
    args = (jax.ShapeDtypeStruct((P, bs, KH, Dh), dtype),
            jax.ShapeDtypeStruct((P, bs, KH, Dh), dtype),
            jax.ShapeDtypeStruct((M, 2), jnp.int32))
    compiled = jax.jit(block_copy_kernel).lower(
        *on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()
