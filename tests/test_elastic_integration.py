"""Elastic end-to-end integration (reference:
``test/integration/test_elastic_torch.py`` + ``elastic_common.py:33-60``,
SURVEY §4 Pattern 3): actually launch ``horovod_tpu.run`` in elastic mode
with a discovery script and run a committing training loop to completion.
"""

import os
import subprocess
import sys
import textwrap

import pytest

torch = pytest.importorskip("torch")

_TRAIN = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["HVD_REPO"])
    import torch
    import horovod_tpu.torch as hvd
    import horovod_tpu.torch.elastic as elastic
    from horovod_tpu.elastic.state import ObjectState

    hvd.init()

    model = torch.nn.Linear(4, 1)
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    opt = torch.optim.SGD(model.parameters(), lr=0.05)

    state = elastic.TorchState(model=model, optimizer=opt, batch=0)

    @elastic.run
    def train(state):
        while state.batch < 6:
            x = torch.ones(2, 4) * (hvd.rank() + 1)
            loss = model(x).sum()
            opt.zero_grad()
            loss.backward()
            grad = hvd.allreduce(model.weight.grad, op=hvd.Average,
                                 name=f"grad.b{state.batch}")
            model.weight.grad.copy_(grad)
            opt.step()
            state.batch += 1
            state.commit()
        return state.batch

    batches = train(state)
    assert batches == 6, batches
    print(f"ELASTIC_RANK_{hvd.rank()}_DONE_{batches}")
""")


def test_elastic_end_to_end(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(_TRAIN)
    discover = tmp_path / "discover.sh"
    discover.write_text("#!/bin/sh\necho localhost:2\n")
    discover.chmod(0o755)

    env = dict(os.environ)
    env["HVD_REPO"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run",
         "-np", "2", "--min-np", "2",
         "--host-discovery-script", str(discover),
         "--cycle-time-ms", "1.0",
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ELASTIC_RANK_0_DONE_6" in proc.stdout
    assert "ELASTIC_RANK_1_DONE_6" in proc.stdout


_CHURN_TRAIN = textwrap.dedent("""
    import os, sys, time
    sys.path.insert(0, os.environ["HVD_REPO"])
    import torch
    import horovod_tpu.torch as hvd
    import horovod_tpu.torch.elastic as elastic

    LOG = os.environ["CHURN_LOG"]
    TARGET = int(os.environ.get("CHURN_TARGET", "16"))

    def log_line(text):
        with open(LOG, "a") as f:
            f.write(text + "\\n")

    hvd.init()
    model = torch.nn.Linear(4, 1)
    # No pre-loop broadcast_parameters: state.sync() broadcasts model and
    # optimizer state, and an extra broadcast would desynchronize a fresh
    # worker joining mid-job (same rule as the reference's elastic docs).
    opt = torch.optim.SGD(model.parameters(), lr=0.05)
    state = elastic.TorchState(model=model, optimizer=opt, batch=0)

    @elastic.run
    def train(state):
        while state.batch < TARGET:
            x = torch.ones(2, 4) * (hvd.rank() + 1)
            loss = model(x).sum()
            opt.zero_grad()
            loss.backward()
            grad = hvd.allreduce(model.weight.grad, op=hvd.Average,
                                 name=f"grad.b{state.batch}")
            model.weight.grad.copy_(grad)
            opt.step()
            state.batch += 1
            log_line(f"BATCH {state.batch} RANK {hvd.rank()} "
                     f"SIZE {hvd.size()}")
            time.sleep(0.25)
            state.commit()
        return state.batch

    batches = train(state)
    log_line(f"DONE RANK {hvd.rank()} BATCHES {batches}")
    print(f"CHURN_RANK_{hvd.rank()}_DONE_{batches}")
""")


def _wait_for(predicate, timeout, what):
    import time

    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.25)
    raise AssertionError(f"timed out waiting for {what}")


def _read_log(path):
    try:
        with open(path) as f:
            return f.read()
    except FileNotFoundError:
        return ""



@pytest.mark.full
def test_elastic_scale_up_then_down(tmp_path):
    """Real host churn through a live elastic run (reference
    test/integration/elastic_common.py:33-60): the discovery output grows
    localhost:2 -> localhost:3 mid-training (workers re-rendezvous at size
    3, a third worker joins), then shrinks back (the extra worker is
    removed, survivors re-rendezvous at size 2) and the job completes."""
    hosts = tmp_path / "hosts.txt"
    hosts.write_text("localhost:2\n")
    discover = tmp_path / "discover.sh"
    discover.write_text(f"#!/bin/sh\ncat {hosts}\n")
    discover.chmod(0o755)
    log = tmp_path / "churn.log"
    script = tmp_path / "train.py"
    script.write_text(_CHURN_TRAIN)

    env = dict(os.environ)
    env["HVD_REPO"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    env["CHURN_LOG"] = str(log)
    env["CHURN_TARGET"] = "24"
    # stdout goes to a file, not a PIPE: nobody drains a pipe until the
    # end, and a full pipe buffer would block the launcher's output pumps
    # (and with them the whole driver).
    outfile = tmp_path / "launcher.out"
    with open(outfile, "w") as out_f:
        proc = subprocess.Popen(
            [sys.executable, "-m", "horovod_tpu.run",
             "-np", "2", "--min-np", "2", "--max-np", "3",
             "--host-discovery-script", str(discover),
             "--cycle-time-ms", "1.0",
             sys.executable, str(script)],
            env=env, stdout=out_f, stderr=subprocess.STDOUT, text=True)
        try:
            # Phase 1: both ranks train at size 2.
            _wait_for(lambda: "BATCH 3" in _read_log(log), 120,
                      "initial training progress")
            assert "SIZE 2" in _read_log(log)

            # Phase 2: scale up — discovery now offers a third slot.
            hosts.write_text("localhost:3\n")
            _wait_for(lambda: "SIZE 3" in _read_log(log), 120,
                      "world to grow to 3")

            # Phase 3: scale down — third slot disappears; survivors
            # continue.
            mark = len(_read_log(log))
            hosts.write_text("localhost:2\n")
            _wait_for(lambda: "SIZE 2" in _read_log(log)[mark:], 120,
                      "world to shrink to 2")

            proc.wait(timeout=180)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    out = _read_log(outfile)
    assert proc.returncode == 0, out
    text = _read_log(log)
    assert "CHURN_RANK_0_DONE_24" in out, out
    # Ranks trained at every world size along the schedule.
    assert "SIZE 2" in text and "SIZE 3" in text, text



@pytest.mark.full
def test_elastic_worker_failure_recovery(tmp_path):
    """A worker dies mid-training: survivors hit HorovodInternalError,
    restore the last commit, and re-rendezvous; the host returns after the
    blacklist cooldown, a replacement worker spawns, and the job finishes
    cleanly (reference elastic failure path, common/elastic.py:147-168 +
    registration blacklisting)."""
    hosts = tmp_path / "hosts.txt"
    hosts.write_text("localhost:2\n")
    discover = tmp_path / "discover.sh"
    discover.write_text(f"#!/bin/sh\ncat {hosts}\n")
    discover.chmod(0o755)
    log = tmp_path / "churn.log"
    marker = tmp_path / "died.once"
    script = tmp_path / "train.py"
    # Rank 1 kills itself at batch 3 on its first life only. (_CHURN_TRAIN
    # is already dedented: the loop body sits at 8 spaces.)
    injected = _CHURN_TRAIN.replace(
        "        state.batch += 1\n",
        "        if (hvd.rank() == 1 and state.batch == 3\n"
        f"                and not os.path.exists({str(marker)!r})):\n"
        f"            open({str(marker)!r}, 'w').close()\n"
        "            os._exit(13)\n"
        "        state.batch += 1\n")
    assert injected != _CHURN_TRAIN, "failure-injection anchor not found"
    script.write_text(injected)

    env = dict(os.environ)
    env["HVD_REPO"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"
    env["CHURN_LOG"] = str(log)
    env["CHURN_TARGET"] = "8"
    proc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.run",
         "-np", "2", "--min-np", "2",
         "--host-discovery-script", str(discover),
         "--blacklist-cooldown-range", "1", "3",
         "--cycle-time-ms", "1.0",
         sys.executable, str(script)],
        env=env, capture_output=True, text=True, timeout=360)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert marker.exists(), "the failure injection never fired"
    text = _read_log(log)
    assert "DONE RANK 0 BATCHES 8" in text, text
    assert "DONE RANK 1 BATCHES 8" in text, text


_KERAS_TRAIN = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, os.environ["HVD_REPO"])
    import numpy as np
    import keras
    import horovod_tpu.keras as hvd
    from horovod_tpu.keras import elastic

    hvd.init()

    model = keras.Sequential([
        keras.layers.Input(shape=(4,)),
        keras.layers.Dense(1),
    ])
    model.compile(
        optimizer=hvd.DistributedOptimizer(
            keras.optimizers.SGD(learning_rate=0.05)),
        loss="mse")

    state = elastic.KerasState(model, batch=0, epoch=0)
    rng = np.random.RandomState(0)
    x = rng.rand(64, 4).astype("float32")
    y = (x.sum(axis=1, keepdims=True) * 0.5).astype("float32")

    @elastic.run
    def train(state):
        state.model.fit(
            x, y, batch_size=16, steps_per_epoch=4,
            epochs=3 - state.epoch,
            callbacks=[
                elastic.CommitStateCallback(state, batches_per_commit=2),
                elastic.UpdateBatchStateCallback(state),
                elastic.UpdateEpochStateCallback(state),
            ],
            verbose=0)

    train(state)
    assert state.epoch == 2, state.epoch
    print(f"KELASTIC_RANK_{hvd.rank()}_DONE")
""")


def test_elastic_keras_end_to_end(tmp_path):
    """Keras flavor of the elastic integration (reference per-framework
    test_elastic_* pattern, SURVEY §4 Pattern 3): hvdrun elastic launch,
    KerasState + Commit/Update callbacks through real fit epochs on
    every rank."""
    pytest.importorskip("keras")
    script = tmp_path / "ktrain.py"
    script.write_text(_KERAS_TRAIN)
    discover = tmp_path / "discover.sh"
    discover.write_text("#!/bin/sh\necho localhost:2\n")
    discover.chmod(0o755)

    env = dict(os.environ)
    env["HVD_REPO"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    env["JAX_PLATFORMS"] = "cpu"

    def launch():
        return subprocess.run(
            [sys.executable, "-m", "horovod_tpu.run",
             "-np", "2", "--min-np", "2",
             "--host-discovery-script", str(discover),
             "--cycle-time-ms", "1.0",
             sys.executable, str(script)],
            env=env, capture_output=True, text=True, timeout=300)

    proc = launch()
    if proc.returncode != 0 and "failed to listen on port" in (
            proc.stdout + proc.stderr):
        # The launcher picks the controller port by binding port 0 and
        # closing it (run/launch.py::free_port); rank 0 binds it again
        # only after it has imported TensorFlow, and on a loaded box
        # another process can be handed the port in between. That race is
        # the launcher's (ROADMAP D0), not what this test holds: launch
        # once more.
        proc = launch()
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "KELASTIC_RANK_0_DONE" in proc.stdout
    assert "KELASTIC_RANK_1_DONE" in proc.stdout
