"""Shared multi-process worker harness for Pattern-1 tests (SURVEY §4):
N subprocesses form a real controller/ring world, each asserts its own
results and prints ``{sentinel}_{rank}_OK``.

One launcher for every such test so the launch protocol (env block, port
handling, cleanup) evolves in lockstep — and so a failing/timed-out rank
never leaves its peers orphaned."""

import os
import signal
import socket
import subprocess
import sys
import time

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)

# SIGTERM grace before SIGKILL when tearing down a failed world, and how
# long a SIGKILLed group gets to actually disappear before we declare an
# orphan leak (kernel delivery is fast; the slack is for scheduler lag).
_TERM_GRACE_S = 3.0
_KILL_GRACE_S = 2.0


def _group_alive(pgid: int) -> bool:
    try:
        os.killpg(pgid, 0)
        return True
    except OSError:
        return False


def _terminate_group(proc: "subprocess.Popen") -> None:
    """Terminate-then-kill a worker's whole process group (the worker is
    its own session leader, so grandchildren die with it), then verify
    nothing survived — a hung worker outliving a failed test would squat
    its controller port and wedge every later world."""
    try:
        pgid = os.getpgid(proc.pid)
    except OSError:
        proc.wait()
        return
    try:
        os.killpg(pgid, signal.SIGTERM)
    except OSError:
        pass
    deadline = time.time() + _TERM_GRACE_S
    while time.time() < deadline and proc.poll() is None:
        time.sleep(0.05)
    try:
        os.killpg(pgid, signal.SIGKILL)
    except OSError:
        pass
    proc.wait()  # reap the direct child; grandchildren go to init
    deadline = time.time() + _KILL_GRACE_S
    while time.time() < deadline and _group_alive(pgid):
        time.sleep(0.05)
    if _group_alive(pgid):
        raise RuntimeError(
            f"process group {pgid} survived SIGKILL: orphaned worker "
            f"children outlived a failed run_world")


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# A stolen port manifests as a controller world-join failure: rank 0's
# bind fails outright, or the squatter accepts the connection and the
# job-key hello handshake rejects it — both funnel into these messages.
# Anything else (assertion failures, crashes, timeouts) is a real bug and
# must not be retried away.
_PORT_CLASH_MARKERS = (
    "world join failed",
    "Address already in use",
    "EADDRINUSE",
)


def run_world(tmp_path, script_text, sentinel, size=2, timeout=240,
              args_for_rank=None, attempts=3):
    """Write ``script_text`` and run ``size`` ranks of it.

    Each rank's argv is ``[rank, *args_for_rank(rank, port)]`` (default:
    ``[rank, port]``). Asserts rc==0 and the sentinel for every rank; on
    any failure or timeout the remaining workers are killed before the
    assertion propagates.

    free_port() has a TOCTOU window (another process can bind the port
    between probe and worker startup); failures that look like a port
    clash — and ONLY those — are retried with a fresh port, up to
    ``attempts`` worlds total."""
    script = tmp_path / "worker.py"
    script.write_text(script_text)
    env = dict(os.environ)
    env["HVD_REPO"] = REPO
    if args_for_rank is None:
        args_for_rank = lambda rank, port: [str(port)]  # noqa: E731

    for attempt in range(attempts):
        port = free_port()
        # Each worker leads its own session/process group so that a
        # failed or timed-out world can be torn down TRANSITIVELY: the
        # worker's own subprocesses (launcher-spawned ranks, shelled-out
        # discovery scripts) die with it instead of surviving as orphans.
        procs = [subprocess.Popen(
            [sys.executable, str(script), str(r),
             *[str(a) for a in args_for_rank(r, port)]], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            start_new_session=True)
            for r in range(size)]
        results = []
        try:
            for r, p in enumerate(procs):
                out, _ = p.communicate(timeout=timeout)
                results.append((r, p.returncode, out))
                if p.returncode != 0:
                    break  # peers can't succeed without this rank
        finally:
            for p in procs:
                if p.poll() is None:
                    _terminate_group(p)
        ok = (len(results) == size and
              all(rc == 0 and f"{sentinel}_{r}_OK" in out
                  for r, rc, out in results))
        if ok:
            return
        blob = "".join(out for _, _, out in results)
        if attempt + 1 < attempts and \
                any(m in blob for m in _PORT_CLASH_MARKERS):
            print(f"proc_harness: suspected port clash on port {port} "
                  f"(attempt {attempt + 1}/{attempts}); retrying with a "
                  f"fresh port", file=sys.stderr)
            continue
        for r, rc, out in results:
            assert rc == 0, f"rank {r} failed:\n{out}"
            assert f"{sentinel}_{r}_OK" in out, out
        raise AssertionError(
            f"only {len(results)}/{size} ranks reported")
