"""Layer scopes in the train step.

Each layer kind runs under one ``jax.named_scope`` (``layers.scoped``), so
the HLO ``op_name`` of its operations names the layer and a device trace can
put time down to it.  The scopes must change that metadata and nothing
else: the compiled instructions are the same with them and without.
"""
import contextlib
import re

import jax
import pytest

from repro.configs import get_config
from repro.configs.shapes import train_batch_specs
from repro.launch import cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import jit_train_step, train_in_shardings
from repro.optim import make_optimizer

# The arch whose smoke step runs each scope.  granite-8b keeps the
# rematerialised scan that the chip benchmark's granite-8b-1l runs.
ARCHS = {"granite-8b": dict(remat=True), "deepseek-moe-16b": {},
         "recurrentgemma-2b": {}}
SCOPE_ARCH = {"embed": "granite-8b", "norm": "granite-8b",
              "attention": "granite-8b", "mlp": "granite-8b",
              "lm_head": "granite-8b", "optimizer": "granite-8b",
              "moe": "deepseek-moe-16b", "recurrent": "recurrentgemma-2b"}
WRAPPED = re.compile(r"[\w.-]+\((.*)\)")       # jvp(x), transpose(jvp(x))
METADATA = re.compile(r', metadata=\{(?:[^}"]|"(?:[^"\\]|\\.)*")*\}')
TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames"
                   r"|\d+ .*)$")


def compiled_step(arch: str) -> str:
    """HLO text of the compiled ``--smoke`` train step of ``arch`` on one
    CPU device, batch 2 x 32."""
    cfg = get_config(arch, smoke=True).replace(**ARCHS[arch])
    opt = make_optimizer("adamw", lr=1e-3)
    mesh = make_mesh(jax.devices()[:1])
    specs = train_batch_specs(cfg, 2, 32)
    in_sh, pshapes, oshapes = train_in_shardings(cfg, opt, specs, mesh)
    return jit_train_step(cfg, opt, in_sh, mesh).lower(
        pshapes, oshapes, specs).compile().as_text()


@pytest.fixture(scope="module")
def scoped_text():
    texts = {}

    def get(arch):
        if arch not in texts:
            texts[arch] = compiled_step(arch)
        return texts[arch]
    return get


def scopes_of(op_name: str) -> set:
    """Whole components of an ``op_name`` path, each with its ``jvp(...)``
    and ``transpose(...)`` wrappers taken off."""
    out = set()
    for comp in op_name.split("/"):
        while (m := WRAPPED.fullmatch(comp)):
            comp = m.group(1)
        out.add(comp)
    return out


def instructions(text: str) -> list:
    """The module's lines without per-instruction metadata and without the
    stack-frame and file-location tables that metadata refers to."""
    return [METADATA.sub("", line) for line in text.splitlines()
            if not TABLE.match(line)]


@pytest.mark.parametrize("scope", sorted(SCOPE_ARCH))
def test_scope_reaches_compiled_op_names(scoped_text, scope):
    names = re.findall(r'op_name="([^"]*)"', scoped_text(SCOPE_ARCH[scope]))
    assert any(scope in scopes_of(n) for n in names), scope


def test_scopes_of_takes_whole_components():
    assert "attention" in scopes_of(
        "jit(train_step)/transpose(jvp(attention))/dot_general")
    assert "lm_head" in scopes_of("jit(train_step)/jvp(lm_head)/mul")
    assert "attention" not in scopes_of("jit(train_step)/attention_x/mul")


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_scopes_leave_instructions_unchanged(scoped_text, monkeypatch, arch):
    scoped = scoped_text(arch)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled_step(arch)
    assert plain != scoped                   # the metadata did differ
    assert instructions(plain) == instructions(scoped)


def test_compile_cache_keeps_scoped_and_plain_steps_apart(tmp_path,
                                                          monkeypatch):
    """JAX keys its persistent cache on the program without debug info by
    default, so a step without scopes would read the scoped step's
    executable, op_names and all, and the other way round."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_persistent_cache_min_compile_time_secs")
    was = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache, "CACHE_DIR", tmp_path)
    try:
        cache.use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        cc.reset_cache()
        compiled_step("granite-8b")
        assert list(tmp_path.iterdir())              # written to the cache
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            plain = compiled_step("granite-8b")
        names = re.findall(r'op_name="([^"]*)"', plain)
        assert names and not any(scopes_of(n) & set(SCOPE_ARCH)
                                 for n in names)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
