"""``horovodrun``-equivalent CLI + programmatic launch API (parity:
``horovod/run/runner.py``).

``parse_args`` mirrors the reference's flag groups (``runner.py:218-484``):
basic np/hosts, tuning params (with the ``--no-*`` negation pairs),
autotune, timeline, elastic (incl. ``--elastic-timeout``), stall check
(``--stall-check``/``--no-stall-check``), logging, ssh. ``_run``
dispatches static vs elastic (``runner.py:790-811``) and
``choose_launcher`` reproduces ``run_controller``'s fallback matrix
(``runner.py:732-763``): forced ``--launcher`` choices validate their
prerequisites with descriptive errors, and ``auto`` detects
jsrun-under-LSF → ssh-for-remote-hosts → local fork. (There is no mpirun
to shell out to on TPU; the launcher slot keeps the reference's
pluggable pattern.)

Programmatic use (parity: ``horovod.run.run()``, ``runner.py:824+``)::

    from horovod_tpu.run import run
    results = run(train_fn, args=(1,), np=4)   # list of per-rank returns
"""

from __future__ import annotations

import argparse
import os
import socket
import sys
import tempfile
from typing import List, Optional

from ..common import config as _config
from ..version import __version__
from . import launch as _launch
from .common.util import config_parser, hosts as _hosts
from .http.http_server import RendezvousServer


def check_build(verbose: bool = False) -> str:
    """Capability report (parity: ``horovodrun --check-build``,
    reference ``runner.py:112-146``) — what this installation can
    actually drive, probed live rather than baked at compile time.
    Every probe is guarded: a diagnostic command must never crash on a
    corrupt .so or a backend that fails to start."""
    def mark(flag):
        return "X" if flag else " "

    def importable(mod):
        try:
            __import__(mod)
            return True
        except Exception:
            return False

    try:
        from ..common import native as _native

        native_ok = _native.NativeCore().available
    except Exception:
        native_ok = False
    try:
        import jax  # noqa: F401

        xla_ok = True
    except Exception:
        xla_ok = False
    platform = None
    if verbose and xla_ok:
        # Asked in a child: a chip belongs to one process at a time, and
        # the launcher must not be the one holding it when the workers
        # start.
        import subprocess

        code = ("import jax; d = jax.devices(); "
                "print('BACKEND=%s (%s) x%d' % (d[0].platform, "
                "d[0].device_kind, len(d)))")
        try:
            r = subprocess.run([sys.executable, "-c", code],
                               capture_output=True, text=True, timeout=120)
            out = r.stdout or ""
            if r.returncode == 0 and "BACKEND=" in out:
                platform = out.rsplit("BACKEND=", 1)[1].strip()
            else:
                platform = "unavailable (backend init failed)"
        except subprocess.TimeoutExpired:
            platform = "unavailable (backend init timed out)"

    lines = [
        f"horovod_tpu v{__version__}:",
        "",
        "Available Frameworks:",
        f"    [{mark(xla_ok)}] JAX (native SPMD)",
        f"    [{mark(importable('tensorflow'))}] TensorFlow",
        f"    [{mark(importable('torch'))}] PyTorch",
        f"    [{mark(importable('mxnet'))}] MXNet",
        "",
        "Available Controllers:",
        f"    [{mark(native_ok)}] native TCP star (libhvdtpu.so)",
        "    [X] direct (single-process)",
        "",
        "Available Tensor Operations:",
        f"    [{mark(xla_ok)}] XLA collectives (ICI/DCN)",
        f"    [{mark(native_ok)}] host TCP ring (allreduce/allgatherv/"
        "broadcast/Adasum VHDD)",
        f"    [{mark(native_ok and xla_ok)}] host-via-XLA staging "
        "(HOROVOD_HOST_VIA_XLA)",
        f"    [{mark(xla_ok)}] Pallas flash attention (fwd+bwd)",
    ]
    if platform:
        lines.append("")
        lines.append(f"Default JAX backend: {platform}")
    return "\n".join(lines)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        prog="horovodrun",
        description="TPU-native Horovod-compatible launcher",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("-v", "--version", action="version",
                        version=__version__)
    parser.add_argument("-cb", "--check-build", action="store_true",
                        help="Print the installation's available "
                             "frameworks, controllers, and tensor "
                             "operations, then exit. Handled after the "
                             "full parse, so --verbose works in either "
                             "position.")

    parser.add_argument("-np", "--num-proc", type=int, dest="np",
                        help="Total number of training processes.")
    parser.add_argument("-p", "--ssh-port", type=int, dest="ssh_port",
                        help="SSH port on all hosts.")
    parser.add_argument("--disable-cache", action="store_true",
                        dest="disable_cache",
                        help="Disable the response cache.")
    parser.add_argument("--start-timeout", type=int, dest="start_timeout",
                        default=30,
                        help="Seconds to wait for all processes to start.")
    parser.add_argument("--network-interface", dest="nics",
                        help="Comma-separated NICs for the control plane.")
    parser.add_argument("--output-filename", dest="output_filename",
                        help="Redirect worker output to <dir>/rank.<N>")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("--config-file", dest="config_file",
                        help="YAML config file (same schema as the "
                             "reference's horovodrun config).")

    group_hosts = parser.add_mutually_exclusive_group()
    group_hosts.add_argument("-H", "--hosts", dest="hosts",
                             help="host1:slots,host2:slots list.")
    group_hosts.add_argument("-hostfile", "--hostfile", dest="hostfile",
                             help="Hostfile with 'host slots=N' lines.")

    group_params = parser.add_argument_group("tuning parameter arguments")
    group_params.add_argument("--fusion-threshold-mb", type=int,
                              dest="fusion_threshold_mb",
                              help="Fusion buffer threshold in MB.")
    group_params.add_argument("--cycle-time-ms", type=float,
                              dest="cycle_time_ms",
                              help="Background cycle time in ms.")
    group_params.add_argument("--cache-capacity", type=int,
                              dest="cache_capacity",
                              help="Response cache capacity.")
    group_params.add_argument("--hierarchical-allreduce",
                              action="store_const", const=True,
                              dest="hierarchical_allreduce",
                              help="Force hierarchical (ICIxDCN) allreduce.")
    group_params.add_argument("--no-hierarchical-allreduce",
                              action="store_const", const=False,
                              dest="hierarchical_allreduce",
                              help="Force the flat allreduce path even "
                                   "when a hier mesh exists.")
    group_params.add_argument("--hierarchical-allgather",
                              action="store_const", const=True,
                              dest="hierarchical_allgather",
                              help="Force hierarchical allgather.")
    group_params.add_argument("--no-hierarchical-allgather",
                              action="store_const", const=False,
                              dest="hierarchical_allgather",
                              help="Force the flat allgather path.")

    group_autotune = parser.add_argument_group("autotune arguments")
    group_autotune.add_argument("--autotune", action="store_const",
                                const=True, dest="autotune")
    group_autotune.add_argument("--no-autotune", action="store_const",
                                const=False, dest="autotune")
    group_autotune.add_argument("--autotune-log-file",
                                dest="autotune_log_file")
    group_autotune.add_argument("--autotune-warmup-samples", type=int,
                                dest="autotune_warmup_samples")
    group_autotune.add_argument("--autotune-steps-per-sample", type=int,
                                dest="autotune_steps_per_sample")
    group_autotune.add_argument("--autotune-bayes-opt-max-samples", type=int,
                                dest="autotune_bayes_opt_max_samples")
    group_autotune.add_argument("--autotune-gaussian-process-noise",
                                type=float,
                                dest="autotune_gaussian_process_noise")

    group_timeline = parser.add_argument_group("timeline arguments")
    group_timeline.add_argument("--timeline-filename",
                                dest="timeline_filename",
                                help="Chrome-tracing JSON output path.")
    group_timeline.add_argument("--timeline-mark-cycles",
                                action="store_const", const=True,
                                dest="timeline_mark_cycles")
    group_timeline.add_argument("--no-timeline-mark-cycles",
                                action="store_const", const=False,
                                dest="timeline_mark_cycles")

    group_elastic = parser.add_argument_group("elastic arguments")
    group_elastic.add_argument("--min-np", type=int, dest="min_np",
                               help="Minimum processes (elastic).")
    group_elastic.add_argument("--max-np", type=int, dest="max_np",
                               help="Maximum processes (elastic).")
    group_elastic.add_argument("--slots-per-host", type=int, dest="slots",
                               help="Slots per discovered host (elastic).")
    group_elastic.add_argument("--host-discovery-script",
                               dest="host_discovery_script",
                               help="Script printing 'host:slots' lines; "
                                    "enables elastic mode.")
    group_elastic.add_argument("--blacklist-cooldown-range", type=int,
                               nargs=2, dest="blacklist_cooldown_range",
                               help="Min/max seconds before a blacklisted "
                                    "host may be retried.")
    group_elastic.add_argument("--elastic-timeout", type=int,
                               dest="elastic_timeout",
                               help="Seconds to wait for the elastic "
                                    "world to (re)assemble after a "
                                    "re-scaling event (reference "
                                    "runner.py:360; default 600).")

    group_stall = parser.add_argument_group("stall check arguments")
    group_stall.add_argument("--no-stall-check", action="store_const",
                             const=True, dest="no_stall_check")
    group_stall.add_argument("--stall-check", action="store_const",
                             const=False, dest="no_stall_check",
                             help="Explicitly enable the stall inspector "
                                  "(overrides a config-file disable).")
    group_stall.add_argument("--stall-check-warning-time-seconds", type=int,
                             dest="stall_check_warning_time_seconds")
    group_stall.add_argument("--stall-check-shutdown-time-seconds", type=int,
                             dest="stall_check_shutdown_time_seconds")

    group_log = parser.add_argument_group("logging arguments")
    group_log.add_argument("--log-level", dest="log_level",
                           choices=["TRACE", "DEBUG", "INFO", "WARNING",
                                    "ERROR", "FATAL"])
    group_log.add_argument("--log-hide-timestamp", action="store_const",
                           const=True, dest="log_hide_timestamp")
    group_log.add_argument("--no-log-hide-timestamp", action="store_const",
                           const=False, dest="log_hide_timestamp")

    group_lib = parser.add_argument_group("library arguments")
    group_lib.add_argument("--launcher", dest="launcher", default="auto",
                           choices=["auto", "local", "ssh", "jsrun"],
                           help="Worker launch transport (the reference's "
                                "gloo/mpi/jsrun slot).")
    # Reference-compat no-ops: collectives always run on XLA/native ring.
    group_lib.add_argument("--gloo", action="store_true", help=argparse.SUPPRESS)
    group_lib.add_argument("--mpi", action="store_true", help=argparse.SUPPRESS)

    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="Training command to run.")

    args = parser.parse_args(argv)
    # Track which flags the user set explicitly so the config file never
    # overrides the command line (parity: runner.py override_args).
    # "Explicit" = the parsed value differs from the parser's default —
    # this keeps 0/0.0 explicit AND counts the --no-* negations, whose
    # explicit value is False against a None default (tri-state flags:
    # None = unset, True/False = user-forced either way).
    args._override_args = {
        a.dest for a in parser._actions
        if a.dest not in ("command", "help")
        and getattr(args, a.dest, None) != parser.get_default(a.dest)
    }
    return args


def _hostnames(args) -> List[_hosts.HostInfo]:
    if getattr(args, "hostfile", None):
        return _hosts.parse_hosts(_hosts.parse_host_files(args.hostfile))
    hosts_str = getattr(args, "hosts", None) or \
        f"localhost:{args.np or 1}"
    return _hosts.parse_hosts(hosts_str)


def _controller_addr(host_alloc_plan) -> str:
    """The address workers use to reach the rank-0 coordination services."""
    first = host_alloc_plan[0].hostname
    if _launch.is_local(first):
        return "127.0.0.1"
    return first


def _launcher_addr(plan, nics=None) -> str:
    """Address where workers reach launcher-side services (rendezvous).

    ``nics`` (the --network-interface allowlist, comma string or
    iterable) pins the advertised address to a named interface — the
    reference's NIC-restriction knob (``run/runner.py`` --network-
    interface + the driver service's interface intersection)."""
    if all(_launch.is_local(s.hostname) for s in plan):
        return "127.0.0.1"
    if nics:
        from .common.util.network import get_local_addresses

        allowed = ({n.strip() for n in nics.split(",") if n.strip()}
                   if isinstance(nics, str) else set(nics))
        for name, ip in get_local_addresses():
            if name in allowed:
                return ip
        raise ValueError(
            f"--network-interface {sorted(allowed)} matched no local "
            "interface with an IPv4 address")
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return socket.gethostname()


def _job_env(args, base_env: Optional[dict] = None) -> dict:
    """CLI-flag → env mapping shared by every launch flavor."""
    env = dict(base_env if base_env is not None else os.environ)
    config_parser.set_env_from_args(env, args)
    if getattr(args, "disable_cache", False):
        env[_config.HOROVOD_CACHE_CAPACITY] = "0"
    if getattr(args, "min_np", None):
        env[_config.HOROVOD_ELASTIC] = "1"
    return env


def _run_static(args, command: List[str], base_env: Optional[dict] = None,
                collect=None) -> int:
    hosts = _hostnames(args)
    np_ = args.np or sum(h.slots for h in hosts)
    plan = _hosts.get_host_assignments(hosts, np_)

    # Fail fast with named hosts before any worker launches (reference
    # runner.py:641-648 ssh check). Probe only hosts the plan actually
    # assigns ranks to — trailing unused hosts must not block a launch.
    _launch.check_ssh_all_hosts({s.hostname for s in plan},
                                ssh_port=getattr(args, "ssh_port", None))

    rendezvous = RendezvousServer(verbose=1 if args.verbose else 0)
    rendezvous_port = rendezvous.start_server()
    rendezvous.init(plan)
    controller_port = _launch.free_port()
    addr = _controller_addr(plan)

    env = _job_env(args, base_env)

    try:
        codes = _launch.launch_workers(
            plan, command, controller_addr=addr,
            controller_port=controller_port,
            rendezvous_addr=_launcher_addr(
                plan, getattr(args, 'nics', None)),
            rendezvous_port=rendezvous_port,
            ssh_port=getattr(args, "ssh_port", None), base_env=env,
            output_filename=getattr(args, "output_filename", None))
        if collect is not None and max(codes, default=1) == 0:
            collect(rendezvous, np_)
    finally:
        rendezvous.stop_server()
    return max(codes) if codes else 0


def _run_elastic(args, command: List[str],
                 base_env: Optional[dict] = None) -> int:
    from .elastic.runner import run_elastic

    return run_elastic(args, command, base_env)


def choose_launcher(args, hosts: List[_hosts.HostInfo]) -> str:
    """Pick the worker-launch transport (the reference's
    ``run_controller`` gloo→mpi→jsrun fallback matrix,
    ``run/runner.py:732-763``, mapped to this launcher's slots):

    - forced choices (``--launcher jsrun/ssh/local``) are validated and
      fail with a descriptive error when their prerequisite is missing
      (the reference's "Gloo support has not been built" pattern);
    - ``auto`` detects: **jsrun** inside an LSF allocation with the
      binary installed → **ssh** when the host plan reaches remote
      hosts → **local** fork otherwise.
    """
    from . import js_run
    from .util.lsf import LSFUtils

    choice = getattr(args, "launcher", "auto") or "auto"
    remote = sorted({h.hostname for h in hosts
                     if not _launch.is_local(h.hostname)})
    if choice == "jsrun":
        if not LSFUtils.using_lsf():
            raise ValueError(
                "--launcher jsrun requested but this process is not "
                "inside an LSF allocation (LSB_* env missing); run under "
                "bsub or use --launcher ssh/local")
        if not js_run.is_jsrun_installed():
            raise ValueError(
                "--launcher jsrun requested but the jsrun binary is not "
                "on PATH")
        return "jsrun"
    if choice == "local":
        if remote:
            raise ValueError(
                "--launcher local requested but the host plan reaches "
                f"remote hosts {remote[:3]}; use --launcher ssh")
        return "local"
    if choice == "ssh":
        return "ssh"
    # auto: scheduler first, then topology.
    if LSFUtils.using_lsf() and js_run.is_jsrun_installed():
        return "jsrun"
    return "ssh" if remote else "local"


def _run(args) -> int:
    if getattr(args, "check_build", False):
        print(check_build(verbose=getattr(args, "verbose", False)))
        return 0
    config_parser.load_config_file(args, getattr(args, "_override_args",
                                                 set()))
    command = list(args.command)
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        raise ValueError("no training command given")
    if getattr(args, "host_discovery_script", None) or \
            getattr(args, "min_np", None):
        # Elastic: discovery script, or fixed hosts with --min-np (the
        # reference's FixedHosts flavor, run/elastic/discovery.py).
        return _run_elastic(args, command)
    # LSF defaults (parity: runner.py:790 _run LSF branch): inside an
    # allocation the host list and np come from the scheduler.
    from .util.lsf import LSFUtils

    if LSFUtils.using_lsf() and not (args.hosts or args.hostfile):
        args.hosts = LSFUtils.get_hosts_string()
        if args.np is None:
            args.np = LSFUtils.get_num_processes()
    if args.np is None and not (args.hosts or args.hostfile):
        raise ValueError("-np (or -H/--hostfile) is required")
    launcher = choose_launcher(args, _hostnames(args))
    if args.verbose:
        print(f"hvdrun: using the {launcher} launcher", file=sys.stderr)
    if launcher == "jsrun":
        return _run_jsrun(args, command)
    return _run_static(args, command)


def _run_jsrun(args, command: List[str]) -> int:
    """Launch through LSF's jsrun (parity: ``run/js_run.py``): one jsrun
    invocation with an ERF rankfile; workers pick ranks up from the
    JSM/PMIX env and rendezvous over HTTP as usual."""
    from . import js_run

    hosts = _hostnames(args)
    np_ = args.np or sum(h.slots for h in hosts)
    plan = _hosts.get_host_assignments(hosts, np_)
    rendezvous = RendezvousServer(verbose=1 if args.verbose else 0)
    rendezvous_port = rendezvous.start_server()
    rendezvous.init(plan)

    env = _job_env(args)
    env[_config.HOROVOD_SIZE] = str(np_)
    env[_config.HOROVOD_RENDEZVOUS_ADDR] = _launcher_addr(
        plan, getattr(args, 'nics', None))
    env[_config.HOROVOD_RENDEZVOUS_PORT] = str(rendezvous_port)
    env[_config.HOROVOD_CONTROLLER_ADDR] = _controller_addr(plan)
    env[_config.HOROVOD_CONTROLLER_PORT] = str(_launch.free_port())
    # Rank order in the ERF must match the runner's plan, and the world is
    # exactly np_ ranks even if the allocation is larger.
    plan_hosts: dict = {}
    for slot in plan:
        plan_hosts[slot.hostname] = plan_hosts.get(slot.hostname, 0) + 1
    try:
        return js_run.js_run(
            np_, command, hosts=plan_hosts, env=env,
            output_filename=getattr(args, "output_filename", None),
            verbose=args.verbose)
    finally:
        rendezvous.stop_server()


def run_commandline(argv: Optional[List[str]] = None) -> int:
    return _run(parse_args(argv))


def main() -> None:
    sys.exit(run_commandline())


# ---- programmatic API (parity: horovod.run.run, runner.py:824+) ------------


def run(func, args=(), kwargs=None, np: int = 1,
        hosts: Optional[str] = None, hostfile: Optional[str] = None,
        ssh_port: Optional[int] = None, verbose: bool = False,
        use_cloudpickle: bool = True, env: Optional[dict] = None,
        output_filename: Optional[str] = None,
        network_interface: Optional[str] = None,
        start_timeout: int = 30, disable_cache: bool = False):
    """Run ``func(*args, **kwargs)`` on ``np`` ranks; return the list of
    per-rank return values in rank order (parity:
    ``horovod.run.run()``, reference ``runner.py:824+`` — the
    network_interface/start_timeout/disable_cache knobs mirror the CLI
    flags of the same names)."""
    import cloudpickle

    with tempfile.TemporaryDirectory(prefix="hvdrun_") as tmpdir:
        fn_path = os.path.join(tmpdir, "func.pkl")
        with open(fn_path, "wb") as f:
            cloudpickle.dump((func, tuple(args), dict(kwargs or {})), f)

        ns = argparse.Namespace(
            np=np, hosts=hosts, hostfile=hostfile, ssh_port=ssh_port,
            verbose=verbose, disable_cache=disable_cache, config_file=None,
            min_np=None, output_filename=output_filename,
            start_timeout=start_timeout, nics=network_interface,
            launcher="auto")
        command = [sys.executable, "-m", "horovod_tpu.run.task_fn", fn_path]
        base_env = dict(env if env is not None else os.environ)
        base_env.setdefault("PYTHONPATH", os.pathsep.join(
            p for p in sys.path if p))

        results = [None] * np

        def collect(rendezvous, np_):
            # Workers PUT their pickled return value under /result/rank.N
            # before exiting (task_fn), so by the time launch_workers
            # returns the store is fully populated.
            for r in range(np_):
                blob = rendezvous.get("result", f"rank.{r}")
                if blob is None:
                    raise RuntimeError(f"rank {r} returned no result")
                results[r] = cloudpickle.loads(blob)

        code = _run_static(ns, command, base_env, collect=collect)
        if code != 0:
            raise RuntimeError(f"horovod_tpu.run.run failed with exit code "
                               f"{code}")
        return results
