"""Example smoke tests: run the shipped examples as subprocesses with tiny
sizes (the reference exercises its examples in CI docker images; SURVEY §4).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _example_env():
    from conftest import subprocess_cpu_env

    return subprocess_cpu_env(
        PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))


def _run_example(relpath, *extra, timeout=240):
    env = _example_env()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", relpath), *extra],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)
    assert proc.returncode == 0, \
        f"{relpath} failed:\n{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
    return proc.stdout


def test_pytorch_mnist_example():
    pytest.importorskip("torch")
    out = _run_example("pytorch_mnist.py", "--epochs", "1",
                       "--batch-size", "256")
    assert "accuracy=" in out


def test_pytorch_synthetic_benchmark_tiny():
    pytest.importorskip("torch")
    out = _run_example(
        "pytorch_synthetic_benchmark.py", "--batch-size", "2",
        "--image-size", "64", "--num-classes", "10",
        "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
        "--num-iters", "2")
    assert "Img/sec per device" in out


def test_adasum_small_model_example():
    pytest.importorskip("torch")
    out = _run_example("adasum_small_model.py", "--steps", "30")
    assert "Adasum:" in out and "Average:" in out


@pytest.mark.full
def test_keras_spark_mnist_example(tmp_path):
    pytest.importorskip("keras")
    out = _run_example("keras_spark_mnist.py", "--epochs", "1",
                       "--work-dir", str(tmp_path))
    assert "history:" in out and "predictions column" in out


def test_pytorch_spark_mnist_example(tmp_path):
    pytest.importorskip("torch")
    out = _run_example("pytorch_spark_mnist.py", "--epochs", "1",
                       "--work-dir", str(tmp_path))
    assert "history:" in out


def test_elastic_pytorch_example_single():
    pytest.importorskip("torch")
    out = _run_example("elastic/pytorch_synthetic_elastic.py",
                       "--num-steps", "20")
    assert "elastic training finished" in out


@pytest.mark.full
def test_keras_mnist_example(tmp_path):
    pytest.importorskip("keras")
    out = _run_example("keras_mnist.py", "--epochs", "1",
                       "--checkpoint-dir", str(tmp_path))
    assert "accuracy=" in out


@pytest.mark.full
def test_keras_mnist_advanced_example():
    pytest.importorskip("keras")
    out = _run_example("keras_mnist_advanced.py", "--epochs", "2",
                       "--warmup-epochs", "1")
    assert "accuracy=" in out


def test_pytorch_imagenet_resnet50_tiny(tmp_path):
    pytest.importorskip("torch")
    out = _run_example(
        "pytorch_imagenet_resnet50.py", "--epochs", "1",
        "--batches-per-epoch", "2", "--batch-size", "2",
        "--image-size", "64", "--num-classes", "10",
        "--checkpoint-format", str(tmp_path / "ck-{epoch}.pt"))
    assert "val_acc=" in out
    assert (tmp_path / "ck-1.pt").exists()


@pytest.mark.full
def test_keras_imagenet_resnet50_tiny(tmp_path):
    pytest.importorskip("keras")
    out = _run_example(
        "keras_imagenet_resnet50.py", "--epochs", "1",
        "--steps-per-epoch", "2", "--batch-size", "2",
        "--image-size", "64", "--num-classes", "10",
        "--warmup-epochs", "1", "--checkpoint-dir", str(tmp_path))
    assert "accuracy=" in out


def test_mxnet_mnist_example_gates_cleanly():
    # mxnet is absent in this image: the example must exit with the clear
    # gate message, not a traceback.
    env = _example_env()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", "mxnet_mnist.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 1
    assert "mxnet is not installed" in proc.stderr


def test_mxnet_imagenet_resnet50_gates_cleanly():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "examples", "mxnet_imagenet_resnet50.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 1
    assert "mxnet is not installed" in proc.stderr


def test_keras_rossmann_estimator_example(tmp_path):
    pytest.importorskip("keras")
    pytest.importorskip("pandas")
    out = _run_example("keras_spark_rossmann_estimator.py",
                       "--epochs", "1", "--num-proc", "2",
                       "--work-dir", str(tmp_path), timeout=420)
    assert "validation RMSPE" in out


def test_elastic_pytorch_mnist_example_single():
    pytest.importorskip("torch")
    out = _run_example("elastic/pytorch_mnist_elastic.py", "--epochs", "1",
                       "--batch-size", "512")
    assert "elastic mnist finished" in out


def test_elastic_tf2_synthetic_example_single():
    pytest.importorskip("tensorflow")
    out = _run_example("elastic/tensorflow2_synthetic_elastic.py",
                       "--num-batches", "20")
    assert "img/sec per worker" in out


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_long_context_example(strategy):
    out = _run_example(
        "jax_long_context.py", "--sp", "2", "--seq-len", "64",
        "--d-model", "32", "--n-heads", "4", "--n-layers", "2",
        "--steps", "2", "--strategy", strategy, timeout=420)
    assert "T_local=32" in out
    assert "tokens/s" in out


def test_long_context_example_packed():
    out = _run_example(
        "jax_long_context.py", "--sp", "2", "--seq-len", "64",
        "--d-model", "32", "--n-heads", "4", "--n-layers", "2",
        "--steps", "2", "--packed", "4", timeout=420)
    assert "packed: 4 docs/row" in out
    assert "tokens/s" in out


def test_elastic_keras_mnist_example_single():
    pytest.importorskip("keras")
    out = _run_example("elastic/tensorflow2_keras_mnist_elastic.py",
                       "--epochs", "1", "--batch-size", "64",
                       "--n-samples", "256")
    assert "elastic keras finished" in out
