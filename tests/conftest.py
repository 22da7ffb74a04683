"""Test configuration: 8 virtual CPU devices stand in for an 8-chip slice.

The reference tests distributed correctness by running N processes under
``mpirun`` on one machine (SURVEY §4 Pattern 1). The TPU-native analog is a
single process with 8 virtual CPU devices: the same SPMD programs that run
over ICI on a pod compile and execute over 8 host devices, so every
collective, sharding, and fusion path is exercised.
"""

import os

# Orphan-sweep tag (see _orphan_world_sweep below): every subprocess this
# test session spawns — proc_harness worlds, elastic launches, their
# grandchildren — inherits this env var, so leaked workers are findable
# by scanning /proc at session end. Set before anything forks.
_WORLD_TAG = f"hvdtw-{os.getpid()}"
os.environ["HVD_TEST_WORLD_TAG"] = _WORLD_TAG

# Tests run on the virtual CPU mesh: the sandbox has no accelerator, and
# the chip is reached through the chip tool (python chip_smoke.py), never
# through pytest. Set before jax is imported; subprocesses inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def subprocess_cpu_env(**overrides):
    """Environment for test subprocesses that must run on the CPU
    backend."""
    return dict(os.environ, JAX_PLATFORMS="cpu", **overrides)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "full: slow soak/e2e/multi-process depth — excluded from the "
        "default (fast) profile; run with --full or -m full")
    config.addinivalue_line(
        "markers",
        "slow: heavyweight scale soaks (e.g. the 32-process controller "
        "world) — excluded from the tier-1 gate's -m 'not slow' run")


def pytest_addoption(parser):
    parser.addoption(
        "--full", action="store_true", default=False,
        help="run the full profile (includes tests marked 'full')")


def pytest_collection_modifyitems(config, items):
    """Two profiles (VERDICT r2 item 9): the default run keeps every
    feature covered but finishes fast; ``--full`` (or ``-m full``) adds
    the soak/e2e/multi-process depth."""
    if config.getoption("--full") or "full" in (config.option.markexpr or ""):
        return
    skip = pytest.mark.skip(
        reason="full profile only (pass --full or -m full)")
    for item in items:
        if "full" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    yield hvd
    hvd.shutdown()


def _find_tagged_orphans():
    """Processes (other than this one) whose environment carries this
    session's world tag — i.e. test-spawned workers that outlived their
    test. Returns [(pid, cmdline)]."""
    needle = f"HVD_TEST_WORLD_TAG={_WORLD_TAG}".encode()
    me = os.getpid()
    orphans = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == me:
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                if needle not in f.read():
                    continue
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(
                    errors="replace").strip()
        except OSError:
            continue  # raced an exit, or not ours to read
        if "multiprocessing.resource_tracker" in cmd or \
                "multiprocessing.semaphore_tracker" in cmd:
            # Python's own tracker daemon, started in THIS interpreter
            # the first time a test touches multiprocessing (e.g.
            # test_spark_run's spawn-context pools). It inherits the
            # session tag and legitimately outlives session teardown —
            # it dies with the interpreter, not with a test world.
            continue
        orphans.append((int(entry), cmd))
    return orphans


def tagged_shm_segments(tag=None):
    """Leaked /dev/shm segments from this session's worlds: the native
    shm transport tags every segment name with HVD_TEST_WORLD_TAG
    (sanitized exactly like csrc/hvd/shm_transport.cc NameTag — alnum
    only, max 12 chars). THE one copy of that name rule on the Python
    side; test modules import this instead of re-deriving it."""
    tag = "".join(c for c in (tag if tag is not None else _WORLD_TAG)
                  if c.isalnum())[:12]
    if not tag or not os.path.isdir("/dev/shm"):
        return []
    return [n for n in os.listdir("/dev/shm")
            if n.startswith(f"hvdshm_{tag}_")]


_find_tagged_shm_segments = tagged_shm_segments


@pytest.fixture(scope="session", autouse=True)
def _orphan_world_sweep():
    """Fail the session LOUDLY — listing PIDs — if chaos/elastic tests
    leaked worker processes (docs/liveness.md; a known tier-1 killer on
    shared boxes: an orphaned world squats its controller port and holds
    CPU, wedging every later multi-process test). The leaked processes
    are killed so one bad test doesn't poison the machine, but the
    failure is still raised: a leak is a bug in the test's teardown, not
    something to mop up silently. Same contract for leaked /dev/shm
    segments (docs/shm-transport.md): swept, then reported as a
    failure."""
    yield
    import signal as _signal
    import time as _time

    orphans = _find_tagged_orphans()
    if not orphans:
        leaked_shm = _find_tagged_shm_segments()
        if leaked_shm:
            for name in leaked_shm:
                try:
                    os.unlink(os.path.join("/dev/shm", name))
                except OSError:
                    pass
            raise AssertionError(
                f"orphaned shm segments leaked by this session (now "
                f"unlinked): {leaked_shm}\n"
                "A world with the shm transport active failed to tear "
                "down (see csrc/hvd/shm_transport.cc lifecycle and "
                "docs/shm-transport.md).")
        return
    my_pgid = os.getpgid(0)
    for pid, _ in orphans:
        try:
            pgid = os.getpgid(pid)
        except OSError:
            pgid = my_pgid  # already gone / unknowable: kill pid only
        try:
            if pgid != my_pgid:
                os.killpg(pgid, _signal.SIGKILL)
            else:
                # The orphan shares pytest's own process group (a plain
                # Popen child, no setsid): killpg here would SIGKILL the
                # whole test session before this report ever surfaced.
                os.kill(pid, _signal.SIGKILL)
        except OSError:
            pass
    _time.sleep(0.2)
    # The killed workers can no longer unlink their segments; mop those
    # up too before reporting (the process leak is the headline).
    for name in _find_tagged_shm_segments():
        try:
            os.unlink(os.path.join("/dev/shm", name))
        except OSError:
            pass
    listing = "\n".join(f"  pid {pid}: {cmd}" for pid, cmd in orphans)
    raise AssertionError(
        f"orphaned test workers leaked by this session (now killed):\n"
        f"{listing}\n"
        "A chaos/elastic test failed to tear down its world — fix its "
        "cleanup (see tests/proc_harness.py group teardown).")
