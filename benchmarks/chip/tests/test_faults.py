"""A run with the timed path broken underneath comes out not correct.

Each case replaces the program's train step (``repro.launch.steps.
make_train_step``, which ``jit_train_step`` builds on) with a faulty one,
then drives the rest of a run on the CPU at a size the test holds, past
the harness's look for a chip, with the limits of
``granite-8b-1l.train_4k``.
"""
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import tiny
from chipbench import harness, reference
from repro.launch import steps


def unchanged(orig):
    def make(cfg, opt, mesh=None, rules=None):
        step = orig(cfg, opt, mesh, rules)

        def train_step(p, o, b):
            _, _, metrics = step(p, o, b)
            return p, o, metrics
        return train_step
    return make


def half_batch(orig):
    def make(cfg, opt, mesh=None, rules=None):
        step = orig(cfg, opt, mesh, rules)

        def train_step(p, o, b):
            return step(p, o, {k: v[: v.shape[0] // 2] for k, v in b.items()})
        return train_step
    return make


def no_exchange(orig):
    """Each data-parallel replica steps on its own rows; the mean of the
    gradients over replicas is left out."""
    def make(cfg, opt, mesh=None, rules=None):
        local = orig(cfg, opt, None, rules)

        def train_step(p, o, b):
            return jax.shard_map(
                local, mesh=mesh, in_specs=(P(), P(), P("data")),
                out_specs=(P(), P(), P()), check_vma=False)(p, o, b)
        return train_step
    return make


def altered_update(orig):
    """One leaf's update is doubled where it is made."""
    def make(cfg, opt, mesh=None, rules=None):
        step = orig(cfg, opt, mesh, rules)

        def train_step(p, o, b):
            new, o2, metrics = step(p, o, b)
            new = dict(new, lm_head=p["lm_head"]
                       + 2 * (new["lm_head"] - p["lm_head"]))
            return new, o2, metrics
        return train_step
    return make


def fp8_reference(orig):
    """The control: the reference put in the program's place, computing in
    float8_e4m3fn, the type below the configuration's bfloat16."""
    def make(cfg, opt, mesh=None, rules=None):
        m = dict(tiny.MODEL, n_layers=cfg.n_layers)

        def train_step(p, o, b):
            total, g = jax.value_and_grad(reference.loss_sum)(
                p, b["tokens"], b["labels"], m, jnp.float8_e4m3fn)
            n = b["tokens"].size
            t = o["step"] + 1
            out = jax.tree_util.tree_map(
                lambda a, gg, mu, nu: reference.adamw(
                    a, gg / n, mu, nu, t.astype(jnp.float32), tiny.OPT),
                p, g, o["mu"], o["nu"])
            pick = [jax.tree_util.tree_map(
                lambda x, k=k: x[k], out,
                is_leaf=lambda x: isinstance(x, tuple)) for k in range(3)]
            return pick[0], {"step": t, "mu": pick[1], "nu": pick[2]}, {
                "loss": total / n}
        return train_step
    return make


def _run(cell, chips):
    return harness.run_cell(cell, jax.devices()[:chips], 2**31 + 1001, 0.3,
                            False, time.perf_counter(), log=lambda *a, **k: 0)


@pytest.mark.parametrize("chips", [1, 4])
def test_sound_run_is_correct(chips):
    r = _run(tiny.cell(chips=chips), chips)
    assert r["correct"], r["checks"]


@pytest.mark.parametrize("fault,chips", [
    (unchanged, 1), (half_batch, 1), (altered_update, 1), (fp8_reference, 1),
    (unchanged, 4), (half_batch, 4), (no_exchange, 4)])
def test_broken_step_is_not_correct(monkeypatch, fault, chips):
    monkeypatch.setattr(steps, "make_train_step",
                        fault(steps.make_train_step))
    r = _run(tiny.cell(chips=chips), chips)
    assert not r["correct"], r["checks"]
