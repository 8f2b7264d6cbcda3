"""Parallel sweep engine: determinism (serial == parallel) and wiring."""
import os

from repro.core import sweep
from repro.core.events import Op, StepTemplate, ps_resources
from repro.core.simulator import SimConfig


def _tasks(workers=(1, 2), n_runs=2, steps_per_worker=10):
    ops = [Op("d", "downlink", size=2e6),
           Op("f", "worker", duration=0.01, deps=(0,)),
           Op("u", "uplink", size=1e6, deps=(1,))]
    tpls = [StepTemplate(ops=ops)]
    tasks = []
    for w in workers:
        for i in range(n_runs):
            cfg = SimConfig(resources=ps_resources(1e8),
                            steps_per_worker=steps_per_worker,
                            warmup_steps=2, seed=7919 + 101 * i,
                            service_jitter=0.1)
            tasks.append((cfg, tpls, w, 32, 2))
    return tasks


def test_parallel_map_identical_to_serial():
    tasks = _tasks()
    serial = [sweep.simulate_task(t) for t in tasks]
    par = sweep.parallel_map(sweep.simulate_task, tasks)
    assert par == serial  # bit-identical: every task carries its own seed


def test_parallel_map_preserves_order():
    assert sweep.parallel_map(abs, [-3, -1, -2]) == [3, 1, 2]


def test_pool_workers_keep_jax_on_cpu(monkeypatch):
    """A worker never reaches for the chip its parent may hold."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("REPRO_SWEEP_SERIAL", raising=False)
    assert sweep.parallel_map(os.getenv, ["JAX_PLATFORMS"] * 2,
                              max_workers=2) == ["cpu", "cpu"]


def test_simulation_pool_reuses_executor():
    tasks = _tasks()
    serial = [sweep.simulate_task(t) for t in tasks]
    # explicit max_workers: the default collapses to the serial fallback
    # on single-CPU hosts, which never materializes an executor
    with sweep.SimulationPool(max_workers=2) as pool:
        a = pool.map(tasks)
        b = pool.map(tasks)       # second batch reuses the executor
        assert pool._executor is not None
    assert pool._executor is None  # context exit released the workers
    assert a == serial and b == serial


def test_simulate_all_batch_mode_identical():
    """batch=True routes through the lockstep engine bit-identically."""
    tasks = _tasks(workers=(2, 4), n_runs=3)
    serial = sweep.simulate_all(tasks, parallel=False)
    assert sweep.simulate_all(tasks, batch=True) == serial
    assert sweep.simulate_batched(tasks, engine="scalar") == serial


def test_ambient_pool_context():
    """sweep.pool() installs one shared pool that simulate_all reuses,
    and restores the previous state (even nested) on exit."""
    tasks = _tasks()
    serial = sweep.simulate_all(tasks, parallel=False)
    assert sweep._ambient_pool is None
    with sweep.pool(max_workers=2) as p:
        assert sweep._ambient_pool is p
        got = sweep.simulate_all(tasks)          # rides the ambient pool
        assert p._executor is not None           # really went through it
        with sweep.pool(parallel=False) as inner:
            assert sweep._ambient_pool is inner
            assert sweep.simulate_all(tasks) == serial
        assert sweep._ambient_pool is p
    assert sweep._ambient_pool is None
    assert p._executor is None                   # exit closed the workers
    assert got == serial


def test_serial_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_SWEEP_SERIAL", "1")
    tasks = _tasks(workers=(1,), n_runs=1)
    assert sweep.parallel_map(sweep.simulate_task, tasks) == \
        [sweep.simulate_task(t) for t in tasks]


class _FakeRun:
    """Minimal PredictionRun stand-in: only what sweep.predict_many needs."""

    def __init__(self):
        self.sim_steps_templates = [StepTemplate(ops=[
            Op("d", "downlink", size=2e6),
            Op("f", "worker", duration=0.01, deps=(0,)),
            Op("u", "uplink", size=1e6, deps=(1,))])]
        self.batch_size = 32
        self.warmup_steps = 2

    def prediction_tasks(self, num_workers, n_runs=3):
        tasks = []
        for i in range(n_runs):
            cfg = SimConfig(resources=ps_resources(1e8),
                            steps_per_worker=10, warmup_steps=2,
                            seed=7919 + 101 * i, service_jitter=0.1)
            tasks.append((cfg, self.sim_steps_templates, num_workers,
                          self.batch_size, self.warmup_steps))
        return tasks


def test_predict_many_serial_equals_parallel():
    run = _FakeRun()
    ser = sweep.predict_many(run, (1, 2, 3), n_runs=2, parallel=False)
    par = sweep.predict_many(run, (1, 2, 3), n_runs=2, parallel=True)
    assert ser == par
    assert set(ser) == {1, 2, 3}
    assert all(v > 0 for v in ser.values())
