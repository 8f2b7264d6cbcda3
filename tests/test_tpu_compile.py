"""Compiles for a described TPU v5e: the chip's compilers, no chip attached.

These catch what interpret-mode tests cannot: a kernel block layout that
Mosaic refuses, and a train step that does not fit one chip's HBM.  The
topology is described inside module-scoped fixtures, never at import: only
one process at a time may load the TPU library, and it keeps it until it
exits, so every test here compiles in this process.
"""
import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import ARCH_IDS, get_config, get_optimizer_name
from repro.configs.shapes import train_batch_specs
from repro.kernels.flash_attention import fits
from repro.kernels.moe_rows import KERNEL_NAMES as ROW_KERNEL_NAMES
from repro.kernels.ops import flash_attention, rglru_scan
from repro.launch.mesh import make_mesh
from repro.launch.steps import jit_train_step, train_in_shardings
from repro.models import layers, moe
from repro.optim import make_optimizer
from repro.parallel import use_mesh

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


MOSAIC = 'custom_call_target="tpu_custom_call"'


def _kernel_compiles(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert MOSAIC in text   # the Mosaic kernel, not interpreted
    return text


def _score_buffers(text: str, b: int, kv: int, s: int):
    """Array types in HLO ``text`` that hold an (S, S) score block for every
    kv head of the batch: two dims of S, at least B * Kv * S * S
    elements."""
    out = set()
    for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]+)\]", text):
        n = [int(x) for x in dims.split(",")]
        if n.count(s) >= 2 and math.prod(n) >= b * kv * s * s:
            out.add(f"{dt}[{dims}]")
    return out


@pytest.mark.parametrize("b,s,h,kv,d,window", [
    (1, 4096, 32, 8, 128, 0),       # granite-8b: causal GQA
    (1, 4096, 10, 1, 256, 2048),    # recurrentgemma-2b: local MQA
])
def test_flash_attention_compiles(one_chip, b, s, h, kv, d, window):
    _kernel_compiles(lambda q, k, v: flash_attention(q, k, v, True, window),
                     one_chip, ((b, s, h, d), jnp.bfloat16),
                     ((b, s, kv, d), jnp.bfloat16),
                     ((b, s, kv, d), jnp.bfloat16))


# Every (heads, kv heads, head_dim) of the zoo whose causal self-attention
# the fused kernel takes on a TPU (``layers.fused_attention_fits``).
ZOO_LAYOUTS = sorted({(c.n_heads, c.n_kv, c.head_dim): a for a, c in (
    (a, get_config(a)) for a in ARCH_IDS)
    if {"attn", "moe"} & set(c.pattern)
    and fits(4096, 4096, c.n_heads // c.n_kv, c.head_dim)}.items())


@pytest.mark.parametrize("h,kv,d", [lay for lay, _ in ZOO_LAYOUTS],
                         ids=[a for _, a in ZOO_LAYOUTS])
def test_flash_attention_grad_compiles(one_chip, h, kv, d):
    """At 2 x 4096 with each head layout of the zoo: forward, dQ and dK/dV
    are Mosaic kernels, and no array of the gradient holds the S x S
    scores."""
    b, s = 2, 4096

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, 0).astype(jnp.float32))

    text = _kernel_compiles(jax.grad(loss, (0, 1, 2)), one_chip,
                            ((b, s, h, d), jnp.bfloat16),
                            ((b, s, kv, d), jnp.bfloat16),
                            ((b, s, kv, d), jnp.bfloat16))
    assert text.count(MOSAIC) >= 3
    assert not _score_buffers(text, b, kv, s)


def test_rglru_scan_compiles(one_chip):
    # recurrentgemma-2b recurrence width R = 2560
    shape = ((2, 4096, 2560), jnp.float32)
    _kernel_compiles(rglru_scan, one_chip, shape, shape)


def test_granite_train_step_fits_one_chip(topo):
    """granite-8b at published widths cut to 1 layer, batch 2 x 4096, f32
    params + AdamW: the step ``launch.train`` runs on one chip.  Attention
    runs the fused kernels (forward, its recomputation, dQ, dK/dV) and no
    array of the step holds the S x S scores."""
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
    text = compiled.as_text()
    assert text.count(MOSAIC) >= 3
    assert not _score_buffers(text, 2, cfg.n_kv, 4096)


# (case, batch, seq, heads, kv heads, head_dim, window, causal, chips,
# takes the fused kernel)
DISPATCH = [
    ("granite_train_4k", 2, 4096, 32, 8, 128, 0, True, 1, True),
    ("granite_train_512", 16, 512, 32, 8, 128, 0, True, 1, True),
    ("cpu_lowering", 2, 4096, 32, 8, 128, 0, True, 0, False),
    ("windowed", 2, 4096, 32, 8, 128, 2048, True, 1, True),
    ("mellum_sliding", 8, 4096, 32, 4, 128, 1024, True, 1, True),
    ("windowed_2x2", 8, 4096, 32, 4, 128, 1024, True, 4, False),
    ("non_causal", 2, 4096, 32, 8, 128, 0, False, 1, False),
    ("below_threshold", 32, 256, 32, 8, 128, 0, True, 1, False),
    ("one_head_a_kv_head_512", 16, 512, 16, 16, 128, 0, True, 1, False),
    ("one_head_a_kv_head_1024", 8, 1024, 16, 16, 128, 0, True, 1, True),
    ("head_dim_64", 2, 4096, 32, 8, 64, 0, True, 1, False),
    ("group_of_32", 2, 4096, 32, 1, 128, 0, True, 1, False),
    ("partitioned_2x2", 4, 4096, 32, 8, 128, 0, True, 4, False),
]


@pytest.mark.parametrize("case,b,s,h,kv,d,window,causal,chips,fused",
                         DISPATCH, ids=[c[0] for c in DISPATCH])
def test_attention_dispatch(topo, case, b, s, h, kv, d, window, causal,
                            chips, fused):
    """Which path ``layers.attention_block`` takes, from the call's own
    shapes, mask and lowering (``chips`` 0: lowered for the CPU)."""
    cfg = get_config("granite-8b").replace(n_heads=h, n_kv=kv, head_dim=d)
    mesh = make_mesh(topo.devices[:chips]) if chips else None
    if mesh is None:
        place = SingleDeviceSharding(jax.devices("cpu")[0])
    else:
        place = NamedSharding(mesh, PartitionSpec())
    shapes = jax.eval_shape(lambda k: layers.init_attention(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=place),
        shapes)
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16,
                             sharding=place)

    def block(p, x):
        with use_mesh(mesh if chips > 1 else None):
            pos = jnp.arange(x.shape[1])[None]
            return layers.attention_block(p, x, cfg, pos, window=window,
                                          causal=causal)

    text = jax.jit(block).lower(params, x).as_text()
    assert ("tpu_custom_call" in text) == fused
    assert ("flash_attention_fwd" in text) == fused


def mellum_stage():
    """mellum2-12b as the chip benchmark cuts it: one period of 4 layers,
    the 8 experts of chip 0 of an 8-chip expert-parallel group, an eighth
    of the vocabulary."""
    cfg = get_config("mellum2-12b")
    return cfg.replace(n_layers=4, vocab=cfg.vocab // 8,
                       moe=dataclasses.replace(cfg.moe, held=8))


MOE_SCOPES = ("router", "dispatch", "experts", "combine")
# Each row kernel of the MoE layer runs in both scopes: dispatch forward,
# then in the gradient as combine's transpose, and the other way round.
ROW_KERNELS = {(kernel, scope) for kernel in ROW_KERNEL_NAMES
               for scope in ("dispatch", "combine")}


def _row_kernel_scopes(text: str):
    """(kernel, moe sub-scope) of each row kernel call in HLO ``text``."""
    found = set()
    for line in text.splitlines():
        call = re.match(r"\s*(?:ROOT )?%(\w+)[.\d]* = .*" + MOSAIC, line)
        if call and call.group(1) in ROW_KERNEL_NAMES:
            path = re.search(r'op_name="([^"]*)"', line).group(1).split("/")
            # each component without its jvp(...)/transpose(...) wrappers
            path = [re.sub(r"^(?:[\w.-]+\()+|\)+$", "", c) for c in path]
            inner = [c for c in path[path.index("moe"):] if c in MOE_SCOPES]
            found.add((call.group(1), inner[-1] if inner else None))
    return found


@pytest.mark.parametrize("chips,kernel", [(1, "gmm"), (4, "ragged-dot")])
def test_expert_products_dispatch(topo, chips, kernel):
    """One chip runs the held experts' products as the Pallas grouped
    matmul (``gmm``, ``tgmm`` in the backward) and moves the rows with the
    row kernels, inside the ``moe/dispatch`` and ``moe/combine`` scopes,
    with no gather over the whole T * min(k, held) buffer; a mesh of four,
    which a Mosaic kernel cannot be partitioned over, runs
    ``lax.ragged_dot`` and the gathers, and no row kernel."""
    cfg = mellum_stage()
    mesh = make_mesh(topo.devices[:chips])
    place = NamedSharding(mesh, PartitionSpec())
    x = jax.ShapeDtypeStruct((2, 512, cfg.d_model), jnp.bfloat16,
                             sharding=place)
    shapes = jax.eval_shape(lambda k: moe.init_moe(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=place),
        shapes)

    def loss(p, x):
        with use_mesh(mesh if chips > 1 else None):
            y, aux = moe.apply_moe(p, x, cfg)
        return jnp.sum(jnp.square(y.astype(jnp.float32))) + aux["aux_loss"]

    text = jax.jit(jax.grad(loss, (0, 1))).lower(params, x).compile(
    ).as_text()
    names = set(re.findall(r"%(t?gmm)[.\d]* = ", text))
    rows = 2 * 512 * min(cfg.moe.top_k, cfg.moe.n_held)
    buffer_gathers = re.findall(
        r"= bf16\[%d,%d\][^ ]* gather\(" % (rows, cfg.d_model), text)
    if kernel == "gmm":
        assert names == {"gmm", "tgmm"} and "ragged-dot" not in text
        assert _row_kernel_scopes(text) == ROW_KERNELS
        assert not buffer_gathers
    else:
        assert not names and "ragged-dot" in text
        assert not _row_kernel_scopes(text) and buffer_gathers


# Temporaries of that step when the expert rows moved by gathers over the
# whole buffer: the row kernels may not hold more.
MELLUM_TEMP_BYTES = 6_201_294_336


def test_mellum_train_step_fits_one_chip(topo):
    """The mellum2-12b-4l benchmark step, batch 8 x 4096, f32 params +
    AdamW, on one chip: it fits HBM with no more temporaries than the
    gathers took, the sliding and full layers take the fused attention
    kernels, the experts the grouped matmul and the row kernels, and no
    array holds the S x S scores."""
    cfg = mellum_stage()
    opt = make_optimizer("adamw", lr=3e-4)
    mesh = make_mesh(topo.devices[:1])
    specs = train_batch_specs(cfg, 8, 4096)
    in_shardings, pshapes, oshapes = train_in_shardings(cfg, opt, specs,
                                                        mesh)
    compiled = jit_train_step(cfg, opt, in_shardings, mesh).lower(
        pshapes, oshapes, specs).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, used / 2**30
    assert m.temp_size_in_bytes <= MELLUM_TEMP_BYTES, m.temp_size_in_bytes
    text = compiled.as_text()
    for kernel in ("flash_attention_fwd", "flash_attention_dq",
                   "flash_attention_dkv", "gmm", "tgmm") + ROW_KERNEL_NAMES:
        assert re.search(r"%" + kernel + r"[.\d]* = ", text), kernel
    assert not _score_buffers(text, 8, cfg.n_kv, 4096)
