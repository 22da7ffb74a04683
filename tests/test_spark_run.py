"""horovod_tpu.spark.run dispatch (parity: reference spark/runner.py:131 +
SURVEY §4 Pattern 2 mock-based launcher testing): a fake pyspark supplies
the executor surface — ``mapPartitionsWithIndex`` runs each partition on
its own thread, like executors do — and the collective job itself runs
for real: the user fn executes in a subprocess per rank via the task
services (``spark/exec.py``), joins the native controller world, and
allreduces across ranks."""

import sys
import threading
import time
import types

import pytest


class _FakeRDD:
    def __init__(self, items):
        self._items = list(items)

    def map(self, f):
        return _FakeRDD([f(x) for x in self._items])

    def mapPartitionsWithIndex(self, f):
        # One element per partition; each partition on its own thread —
        # the concurrency shape of real executors, which the in-executor
        # transport depends on (tasks block serving until shutdown).
        results = [None] * len(self._items)
        errors = []

        def _one(i, x):
            try:
                results[i] = list(f(i, iter([x])))
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=_one, args=(i, x), daemon=True)
                   for i, x in enumerate(self._items)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        if errors:
            raise errors[0]
        return _FakeRDD([r for part in results if part for r in part])

    def collect(self):
        return list(self._items)


class _FakeSparkContext:
    defaultParallelism = 2
    _active_spark_context = None

    def parallelize(self, seq, num):
        assert num == len(list(seq))
        return _FakeRDD(seq)


@pytest.fixture
def fake_pyspark(monkeypatch):
    mod = types.ModuleType("pyspark")
    ctx = _FakeSparkContext()
    _FakeSparkContext._active_spark_context = ctx
    mod.SparkContext = _FakeSparkContext
    monkeypatch.setitem(sys.modules, "pyspark", mod)
    yield mod
    _FakeSparkContext._active_spark_context = None


def _make_train():
    # Nested so cloudpickle serializes it by value — the executor
    # subprocesses can't import this test module.
    def _train():
        import os

        import numpy as np

        os.environ["JAX_PLATFORMS"] = "cpu"
        import horovod_tpu.torch as hvd

        hvd.init()
        out = hvd.allreduce(
            __import__("torch").ones(3) * (hvd.rank() + 1), op=hvd.Sum)
        r = (hvd.rank(), hvd.size(), float(np.asarray(out)[0]))
        hvd.shutdown()
        return r

    return _train


def test_spark_run_in_executor(fake_pyspark):
    """The full register -> exec -> collect path: fn runs in a subprocess
    per rank (in-executor semantics), the world forms, results return in
    rank order."""
    import horovod_tpu.spark as spark

    results = spark.run(_make_train(), num_proc=2, verbose=0)
    assert len(results) == 2
    assert [r[0] for r in results] == [0, 1]  # rank order
    assert all(r[1] == 2 for r in results)
    assert all(r[2] == 3.0 for r in results)  # 1+2 summed across ranks


@pytest.mark.full
def test_spark_run_ssh_fallback(fake_pyspark):
    """use_ssh=True keeps the hostname-collect + local-launcher path."""
    import horovod_tpu.spark as spark

    results = spark.run(_make_train(), num_proc=2, verbose=0,
                        use_ssh=True)
    assert len(results) == 2
    assert sorted(r[0] for r in results) == [0, 1]
    assert all(r[2] == 3.0 for r in results)


def test_spark_run_reports_task_failure(fake_pyspark):
    import horovod_tpu.spark as spark

    def _boom():
        raise RuntimeError("exploded in executor")

    with pytest.raises(RuntimeError, match="exploded in executor"):
        spark.run(_boom, num_proc=2, verbose=0)


def test_spark_run_requires_active_context(fake_pyspark):
    import horovod_tpu.spark as spark

    _FakeSparkContext._active_spark_context = None
    with pytest.raises(ValueError, match="active SparkContext"):
        spark.run(lambda: None)


def test_spark_run_without_pyspark(monkeypatch):
    import horovod_tpu.spark as spark

    monkeypatch.setitem(sys.modules, "pyspark", None)
    with pytest.raises(ImportError, match="requires pyspark"):
        spark.run(lambda: None)


def test_exec_round_without_spark():
    """spark/exec.py is pyspark-independent: a plain process pool stands
    in for the executors and the full protocol round runs for real."""
    import multiprocessing as mp

    from horovod_tpu.run.common.util import secret
    from horovod_tpu.spark.exec import (
        SparkDriverService, run_via_task_services, task_main)

    key = secret.make_secret_key()
    driver = SparkDriverService(2, key)
    ctx = mp.get_context("spawn")
    procs = [ctx.Process(target=task_main,
                         args=(i, driver.addresses(), key))
             for i in range(2)]
    for p in procs:
        p.start()
    try:
        driver.wait_for_initial_registration(60)

        def _double_with_env(x):
            import os

            return (x * 2, "HOROVOD_RANK" in os.environ)

        results = run_via_task_services(driver, _double_with_env, (21,),
                                        {}, 2, key)
        assert results == [(42, True), (42, True)]
    finally:
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()
        driver.shutdown()


def test_registration_timeout_shuts_down_registered_tasks():
    """A registration timeout (partial world) must still send
    ShutdownRequest to the tasks that DID register — otherwise task_main
    serves wait_for_shutdown(None) forever and leaks its executor slot
    (round-3 advisor finding)."""
    import multiprocessing as mp

    from horovod_tpu.run.common.util import secret
    from horovod_tpu.spark.exec import (
        SparkDriverService, shutdown_registered_tasks, task_main)

    key = secret.make_secret_key()
    driver = SparkDriverService(2, key)  # expects 2, only 1 will register
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=task_main, args=(0, driver.addresses(), key))
    p.start()
    try:
        # The child has to import and register first; on a loaded box
        # that alone can outlast the driver's short timeout, and a task
        # that has not registered is (rightly) sent nothing.
        deadline = time.monotonic() + 300
        while not driver.task_addresses_for_driver(0):
            assert p.is_alive() and time.monotonic() < deadline, \
                "task 0 never registered"
            time.sleep(0.1)
        with pytest.raises(TimeoutError):
            driver.wait_for_initial_registration(2)
        # The fix: the driver's error path shuts down registered tasks.
        shutdown_registered_tasks(driver, 2, key)
        p.join(timeout=30)
        assert not p.is_alive(), \
            "registered task kept serving after the driver gave up"
    finally:
        if p.is_alive():
            p.terminate()
        driver.shutdown()
