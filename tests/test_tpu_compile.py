"""Compiles for a described TPU v5e: the chip's compilers, no chip attached.

These catch what interpret-mode tests cannot: a kernel block layout that
Mosaic refuses, and a train step that does not fit one chip's HBM.  The
topology is described inside module-scoped fixtures, never at import: only
one process at a time may load the TPU library, and it keeps it until it
exits, so every test here compiles in this process.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, get_optimizer_name
from repro.configs.shapes import train_batch_specs
from repro.kernels.ops import flash_attention, rglru_scan
from repro.launch.mesh import make_mesh
from repro.launch.steps import jit_train_step, train_in_shardings
from repro.optim import make_optimizer

# HBM one v5e chip offers a program, as the TPU compiler reports it when it
# refuses a program that does not fit ("Used ...G of 15.75G hbm").
V5E_HBM_BYTES = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    was_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_compiles(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text   # the Mosaic kernel, not interpreted


@pytest.mark.parametrize("b,s,h,kv,d,window", [
    (1, 4096, 32, 8, 128, 0),       # granite-8b: causal GQA
    (1, 4096, 10, 1, 256, 2048),    # recurrentgemma-2b: local MQA
])
def test_flash_attention_compiles(one_chip, b, s, h, kv, d, window):
    _kernel_compiles(lambda q, k, v: flash_attention(q, k, v, True, window),
                     one_chip, ((b, s, h, d), jnp.bfloat16),
                     ((b, s, kv, d), jnp.bfloat16),
                     ((b, s, kv, d), jnp.bfloat16))


def test_rglru_scan_compiles(one_chip):
    # recurrentgemma-2b recurrence width R = 2560
    shape = ((2, 4096, 2560), jnp.float32)
    _kernel_compiles(rglru_scan, one_chip, shape, shape)


def test_granite_train_step_fits_one_chip(topo):
    """granite-8b at published widths cut to 1 layer, batch 2 x 4096, f32
    params + AdamW: the step ``launch.train`` runs on one chip."""
    cfg = get_config("granite-8b").replace(n_layers=1)
    opt = make_optimizer(get_optimizer_name("granite-8b"), lr=3e-4)
    mesh = make_mesh(topo.devices[:1])
    specs = train_batch_specs(cfg, 2, 4096)
    in_shardings, pshapes, oshapes = train_in_shardings(cfg, opt, specs,
                                                        mesh)
    compiled = jit_train_step(cfg, opt, in_shardings, mesh).lower(
        pshapes, oshapes, specs).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used / 2**30
