"""Multi-process launcher — the ``mpirun`` / ``scripts/wrap.sh`` analog.

The reference's whole UX is ``mpirun -n N wrap.sh luajit script.lua``
(``scripts/wrap.sh``, ``scripts/ompirun.sh``): N identical processes, the
world discovered from the environment, per-rank log redirection, and
manual ``pkill`` when a rank died (``dependencies/README.md:46-49``).
This is that launcher, TPU-native:

    python -m torchmpi_tpu.launch --nproc 4 examples/mnist_allreduce.py
    python -m torchmpi_tpu.launch --nproc 2 --cpu-devices 2 train.py -- --lr 0.1

- spawns ``--nproc`` copies of the script (or ``-m module``) with
  ``TORCHMPI_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID`` set;
  ``mpi.start()`` reads them, so an unmodified script becomes rank i of N
  (the MPI_Init-reads-mpirun's-env contract);
- ``--cpu-devices K`` gives each process a K-device virtual CPU mesh
  (XLA_FLAGS + JAX_PLATFORMS=cpu) — the "multi-node without a
  cluster" test mode (SURVEY.md §4);
- ``--log-dir DIR`` writes ``rank_<i>.log`` per process (wrap.sh's
  ``LOG_TO_FILE``); default streams every line prefixed ``[i]``;
- one rank failing kills the rest (no manual pkill) and the launcher
  exits with that rank's code; ``--nnodes/--node-rank/--coordinator``
  extend the same contract across hosts.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import threading
from pathlib import Path
from typing import List, Optional


def arm_supervise_telemetry(args) -> Optional[str]:
    """``--supervise`` without ``--telemetry-live`` would silently
    starve the supervisor: its ONLY sensor is the launcher-resident
    aggregator's streaming verdicts, so a supervised job with the live
    plane dark observes nothing and never acts — the worst failure
    mode, an operator who BELIEVES recovery is armed. Auto-arm the
    plane and return the notice to print (the operator asked for one
    flag and got two, which must be visible in the job log); ``None``
    when nothing had to be armed."""
    if not getattr(args, "supervise", False) or args.telemetry_live:
        return None
    args.telemetry_live = True
    return (
        "[launch] --supervise needs the live telemetry plane (the "
        "streaming verdicts are the supervisor's only sensor): "
        "auto-arming --telemetry-live"
    )


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _stream(proc: subprocess.Popen, rank: int) -> None:
    for line in proc.stdout:  # type: ignore[union-attr]
        sys.stdout.write(f"[{rank}] {line}")
        sys.stdout.flush()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m torchmpi_tpu.launch",
        description="spawn N torchmpi_tpu controller processes (mpirun analog)",
    )
    ap.add_argument("--nproc", type=int, required=True,
                    help="processes to launch on THIS host")
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="give each process a K-device virtual CPU mesh")
    ap.add_argument("--log-dir", default=None,
                    help="write rank_<i>.log files instead of streaming")
    ap.add_argument("--telemetry-dir", default=None,
                    help="enable telemetry in every rank and dump a "
                    "per-rank metrics snapshot + Perfetto trace JSON "
                    "(telemetry_rank_<i>.json / .trace.json) there on exit "
                    "— including abnormal exit (SIGTERM/SIGINT/fault "
                    "handlers); feed the dir to "
                    "`python -m torchmpi_tpu.telemetry.analyze`")
    ap.add_argument("--telemetry-live", action="store_true",
                    help="run a live telemetry aggregator in the launcher "
                    "and stream per-rank telemetry to it while the job "
                    "runs: every rank exports bounded metric/flight deltas "
                    "(over the elastic heartbeat when --elastic, a "
                    "dedicated socket otherwise) and the launcher serves "
                    "fleet-level /metrics (Prometheus), /health, /verdicts "
                    "(streaming desync/straggler/hang/PS verdicts) and "
                    "/calibration over HTTP; watch it with "
                    "`python -m torchmpi_tpu.telemetry.top <addr>`")
    ap.add_argument("--telemetry-live-port", type=int, default=0,
                    help="HTTP scrape port for --telemetry-live "
                    "(default: auto-chosen, printed at startup)")
    ap.add_argument("--telemetry-live-addr-file", default=None,
                    help="write the live plane's addresses here as JSON "
                    "{\"http\": ..., \"ingest\": ...} (atomic), for "
                    "operators and tests")
    ap.add_argument("--watchdog-timeout", type=float, default=0,
                    help="arm the per-rank hang watchdog: a collective or "
                    "PS RPC in flight (or a peer heartbeat stale) longer "
                    "than this many seconds dumps a structured hang report "
                    "(hang_rank_<i>.json, in --telemetry-dir when set)")
    ap.add_argument("--nnodes", type=int, default=1,
                    help="total hosts in the job")
    ap.add_argument("--node-rank", type=int, default=0,
                    help="this host's index in [0, nnodes)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (required when nnodes > 1; "
                    "default: localhost:<free port>)")
    ap.add_argument("--set-constant", action="append", default=[],
                    metavar="NAME=VALUE",
                    help="override a torchmpi_tpu.constants knob in every "
                    "rank (repeatable), e.g. --set-constant ps_replication=2 "
                    "--set-constant parameterserver_wire_dtype=int8. "
                    "Applied by start() before the runtime bootstraps "
                    "(and re-applied over persisted tuned values), so "
                    "fabric knobs like the PS replica-chain length are "
                    "deployable without editing the training script.")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="full-job restarts: when the world dies, relaunch "
                    "ALL ranks up to this many times (scripts see "
                    "TORCHMPI_TPU_RESTART_COUNT and should resume from "
                    "their last checkpoint). Without --elastic, ANY rank "
                    "death triggers the relaunch (the pre-elastic model). "
                    "COMPOSED with --elastic, restart is the LAST "
                    "escalation rung, not an alternative: single deaths "
                    "are survived live by the membership layer, and the "
                    "world only relaunches when live recovery is "
                    "exhausted — every worker dead, or the --supervise "
                    "policy engine decides a checkpoint rollback "
                    "(resize-torn, desync, exhausted single-fault "
                    "contract). Multi-node jobs (--nnodes > 1) negotiate "
                    "the per-attempt coordinator WITHOUT communication: "
                    "attempt k uses --coordinator's port + k, so reserve "
                    "max-restarts consecutive ports above it on the "
                    "coordinator host.")
    ap.add_argument("--elastic", action="store_true",
                    help="LIVE elasticity: run an elastic membership "
                    "coordinator in the launcher, export "
                    "TORCHMPI_TPU_ELASTIC=host:port to every worker, and "
                    "keep the job alive across rank deaths — survivors "
                    "redistribute state through torchmpi_tpu.reshard and "
                    "training continues (no world relaunch). An operator "
                    "`python -m torchmpi_tpu.reshard.elastic grow <addr>` "
                    "spawns one more worker; `shrink` evicts one; `evict "
                    "--mid M` removes a specific member. The launcher "
                    "exits when every worker has; the exit code is the "
                    "LAST worker's. Composes with --max-restarts (the "
                    "checkpoint-rollback rung) and --supervise (autonomous "
                    "recovery). Single-node only.")
    ap.add_argument("--supervise", action="store_true",
                    help="run the verdict-driven recovery supervisor in "
                    "the launcher (requires --elastic; implies "
                    "--telemetry-live): streaming verdicts from the fleet "
                    "aggregator drive a policy table with hysteresis, "
                    "bounded jittered retries and an escalation ladder — "
                    "rank-dead/hang evicts the rank and commits a live "
                    "shrink, stragglers are quarantined (evict + rejoin "
                    "denylist), and resize-torn/desync/exhausted-contract "
                    "roll the world back to the last checkpoint_every "
                    "artifact (give the job restart budget with "
                    "--max-restarts). Actions serve on the live plane's "
                    "/actions endpoint and as tm_supervisor_* metrics; "
                    "knobs: the supervisor_* constants "
                    "(--set-constant supervisor_hysteresis_windows=2 ...)")
    ap.add_argument("--supervise-dry-run", action="store_true",
                    help="with --supervise: journal every recovery "
                    "decision (stderr, /actions, metrics) but actuate "
                    "nothing — the shadow-mode rollout posture. Implies "
                    "--supervise.")
    ap.add_argument("--elastic-addr-file", default=None,
                    help="write the elastic coordinator's host:port here "
                    "(atomic), for operators and tests")
    ap.add_argument("-m", "--module", default=None,
                    help="run a module (python -m) instead of a script")
    ap.add_argument("script", nargs="?", default=None,
                    help="script path (omit when using --module)")
    ap.add_argument("script_args", nargs=argparse.REMAINDER,
                    help="arguments passed through to the script")
    args = ap.parse_args(argv)

    if args.module is not None and args.script is not None:
        # with -m, the `script` positional greedily eats the first
        # passthrough token — everything positional belongs to the module
        args.script_args = [args.script] + args.script_args
        args.script = None
    if (args.script is None) == (args.module is None):
        ap.error("exactly one of a script path or --module is required")
    if args.nproc < 1:
        ap.error(f"--nproc must be >= 1, got {args.nproc}")
    if (
        args.nproc > 1
        and not args.cpu_devices
        and os.environ.get("JAX_PLATFORMS") != "cpu"
    ):
        # measured on a v5e host: the second child dies on libtpu's
        # multi-process lockfile, the first hangs holding the chip
        ap.error(
            f"--nproc {args.nproc} would start {args.nproc} processes that "
            "each initialise the accelerator backend on this host, and a "
            "chip belongs to one process at a time. A single host's chips "
            "are driven by ONE process (mpi.start() sees them all): run "
            "the script directly or with --nproc 1; for a CPU world pass "
            "--cpu-devices K or set JAX_PLATFORMS=cpu"
        )
    if args.nnodes > 1 and args.coordinator is None:
        ap.error("--coordinator host:port is required when nnodes > 1")
    if not 0 <= args.node_rank < args.nnodes:
        ap.error(f"--node-rank {args.node_rank} outside [0, {args.nnodes})")
    if args.max_restarts < 0:
        ap.error(f"--max-restarts must be >= 0, got {args.max_restarts}")
    if args.elastic and args.nnodes > 1:
        ap.error("--elastic requires a single-node job (nnodes == 1)")
    if args.supervise_dry_run:
        args.supervise = True
    if args.supervise and not args.elastic:
        ap.error("--supervise requires --elastic (the supervisor drives "
                 "the elastic membership coordinator)")
    notice = arm_supervise_telemetry(args)
    if notice:
        print(notice, file=sys.stderr)
    if args.watchdog_timeout < 0:
        ap.error(
            f"--watchdog-timeout must be >= 0, got {args.watchdog_timeout}"
        )
    for spec in args.set_constant:
        if "=" not in spec:
            ap.error(f"--set-constant expects NAME=VALUE, got {spec!r}")

    target = (
        [sys.executable, "-m", args.module]
        if args.module
        else [sys.executable, args.script]
    )
    # argparse.REMAINDER keeps a leading "--" separator; drop it
    extra = args.script_args
    if extra and extra[0] == "--":
        extra = extra[1:]

    if args.elastic:
        # Live elasticity first, full-job restart LAST: single deaths
        # are survived in place by the membership layer, so an elastic
        # attempt only ends nonzero when live recovery is exhausted —
        # every worker dead, or the supervisor's rollback rung killed
        # the world on purpose. THAT is what --max-restarts now buys
        # under --elastic: relaunch from the last registered checkpoint
        # (scripts read TORCHMPI_TPU_RESTART_COUNT and the
        # TORCHMPI_TPU_CHECKPOINT_STATE registry to resume).
        # the cross-process last-checkpoint registry root is chosen ONCE,
        # outside the attempt loop: the registered artifact must survive
        # the very restart it exists to serve. A run-scoped temp root
        # (no --telemetry-dir) holds only the registry POINTER, not the
        # artifacts, so it is removed once the job is over.
        import shutil
        import tempfile

        tmp_root = None
        if args.telemetry_dir:
            state_root = Path(args.telemetry_dir)
        else:
            tmp_root = tempfile.mkdtemp(prefix="tm-elastic-state-")
            state_root = Path(tmp_root)
        try:
            for restart in range(args.max_restarts + 1):
                rc = _run_elastic(args, target, extra, restart,
                                  state_root)
                if rc == 0 or rc == 130 or restart == args.max_restarts:
                    return rc
                print(
                    f"[launch] elastic attempt {restart} ended with "
                    f"rc={rc}; relaunching the world from the last "
                    f"checkpoint ({args.max_restarts - restart} "
                    "restart(s) left)",
                    file=sys.stderr,
                )
            return rc
        finally:
            if tmp_root is not None:
                shutil.rmtree(tmp_root, ignore_errors=True)

    # Restart-style recovery = full-job relaunch from the last
    # checkpoint (a controller process cannot rejoin a running
    # jax.distributed job; the reference had no recovery at all — a dead
    # rank meant manual pkill, dependencies/README.md:46-49). Each
    # single-node attempt gets a FRESH auto-chosen coordinator port (the
    # old service's socket may linger); multi-node attempts derive it
    # with ZERO cross-host coordination — attempt k binds --coordinator's
    # port + k on every node, so the hosts re-agree by arithmetic.
    # Scripts read TORCHMPI_TPU_RESTART_COUNT to resume, not cold-start.
    for restart in range(args.max_restarts + 1):
        rc = _run_world(args, target, extra, restart)
        if rc == 0 or rc == 130 or restart == args.max_restarts:
            return rc  # success, operator interrupt, or budget spent
        print(
            f"[launch] attempt {restart} failed with rc={rc}; "
            f"restarting the world "
            f"({args.max_restarts - restart} restart(s) left)",
            file=sys.stderr,
        )
    return rc


def _constants_spec(set_constant) -> str:
    """Merge ``--set-constant`` overrides onto any operator-exported
    TORCHMPI_TPU_CONSTANTS (CLI overrides win: `_apply_env_constants`
    applies entries in order). Replacing instead of merging silently
    dropped the operator's env-specified knobs."""
    ambient = os.environ.get("TORCHMPI_TPU_CONSTANTS", "")
    parts = [s for s in (ambient,) if s] + list(set_constant)
    return ";".join(parts)


def _worker_env(args, rank: int, restart: int = 0) -> dict:
    """Per-rank environment (shared by the static and elastic paths)."""
    env = dict(
        os.environ,
        TORCHMPI_TPU_PROCESS_ID=str(rank),
        TORCHMPI_TPU_RESTART_COUNT=str(restart),
    )
    if args.set_constant:
        env["TORCHMPI_TPU_CONSTANTS"] = _constants_spec(args.set_constant)
    if args.watchdog_timeout:
        env["TORCHMPI_TPU_WATCHDOG"] = str(args.watchdog_timeout)
    if args.cpu_devices:
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_devices}"
        ).strip()
        env["JAX_PLATFORMS"] = "cpu"
    return env


def _start_live_aggregator(args, telemetry_dir):
    """``--telemetry-live``: start the launcher-resident fleet
    aggregator + scrape endpoints; returns it (or None when off)."""
    if not args.telemetry_live:
        return None
    from .telemetry.live import FleetAggregator

    if args.set_constant:
        # the aggregator reads fabric knobs (telemetry_live_interval_s
        # drives its staleness bound) from THIS process's constants —
        # apply the overrides here like _run_elastic does, or workers
        # framing at an overridden cadence read as stale to an
        # aggregator still assuming the default
        os.environ["TORCHMPI_TPU_CONSTANTS"] = _constants_spec(
            args.set_constant
        )
        from .runtime_state import _apply_env_constants

        _apply_env_constants()
    agg = FleetAggregator(
        mark_dir=telemetry_dir,
        # --watchdog-timeout reaches the WORKERS via env; hand it to the
        # aggregator explicitly so the live hang verdict uses the same
        # bound (None = fall back to the constants knob)
        hang_after_s=args.watchdog_timeout or None,
    )
    agg.serve(http_port=args.telemetry_live_port)
    print(
        f"[launch] live telemetry at http://127.0.0.1:{agg.http_port} "
        "(/metrics /health /verdicts /calibration) — watch with "
        f"`python -m torchmpi_tpu.telemetry.top 127.0.0.1:{agg.http_port}`",
        file=sys.stderr,
    )
    if args.telemetry_live_addr_file:
        import json

        path = Path(args.telemetry_live_addr_file)
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(path.name + ".tmp")
        tmp.write_text(json.dumps({
            "http": f"127.0.0.1:{agg.http_port}",
            "ingest": f"127.0.0.1:{agg.ingest_port}",
        }))
        os.replace(tmp, path)
    return agg


def _close_live_aggregator(agg, telemetry_dir) -> None:
    if agg is None:
        return
    if telemetry_dir is not None:
        try:
            # the calibration feed outlives the job: schedule.calibrate()
            # fits the persisted samples offline
            agg.save_samples(Path(telemetry_dir) / "live_samples.json")
        except OSError:
            pass
    agg.close()


def _run_elastic(args, target, extra, restart: int,
                 state_root) -> int:
    """Live-elastic supervision: one membership coordinator in THIS
    process, workers that survive each other's deaths, and an operator
    grow surface that spawns additional workers into the running job.
    Exits when every worker has; returns the last worker's exit code
    (survivors of tolerated deaths exit last, so a recovered job is 0).

    With ``--supervise``, a :class:`~.supervise.RecoverySupervisor`
    consumes the launcher aggregator's streaming verdicts and acts:
    evict (SIGKILL + the membership sweep commits the live shrink),
    grow, or — the last rung — kill the world so the surrounding
    ``--max-restarts`` loop relaunches attempt ``restart + 1`` from the
    last registered checkpoint."""
    from .analysis import lockmon as _lockmon
    from .reshard.elastic import ElasticCoordinator

    if args.set_constant:
        # the membership coordinator lives in THIS process and reads
        # fabric knobs (elastic_heartbeat_seconds, the barrier timeout)
        # from constants — apply the overrides here too, not only in the
        # worker envs, or `--set-constant elastic_heartbeat_seconds=...`
        # would tune the members' beat cadence but not the coordinator's
        # death-detection sweep. Merged onto any operator-exported spec
        # (workers re-merge; the duplicate entries are idempotent).
        os.environ["TORCHMPI_TPU_CONSTANTS"] = _constants_spec(
            args.set_constant
        )
        from .runtime_state import _apply_env_constants

        _apply_env_constants()

    lock = _lockmon.make_lock("launch.py:_run_elastic")
    procs: dict = {}
    readers: List[threading.Thread] = []
    logs = []
    next_rank = [0]
    log_dir = Path(args.log_dir) if args.log_dir else None
    if log_dir is not None:
        log_dir.mkdir(parents=True, exist_ok=True)
    telemetry_dir = Path(args.telemetry_dir) if args.telemetry_dir else None
    if telemetry_dir is not None:
        telemetry_dir.mkdir(parents=True, exist_ok=True)
        # clear liveness/hang artifacts from a PREVIOUS LAUNCH only
        # (attempt 0): on a restart attempt they are the failed
        # attempt's post-mortem — the evidence that explains the very
        # failure that consumed the restart
        if restart == 0:
            for pattern in ("heartbeat_rank_*.json", "hang_rank_*.json",
                            "dead_rank_*.json"):
                for stale in telemetry_dir.glob(pattern):
                    try:
                        stale.unlink()
                    except OSError:
                        pass
    # the cross-process last-checkpoint registry: workers register
    # every checkpoint_every artifact here; the supervisor's rollback
    # rung and a relaunched attempt both read it. The root comes from
    # main()'s restart loop (chosen once, so the registry SURVIVES
    # restart attempts — the artifact is the whole point of the
    # restart); exported into THIS process's env too, or the
    # launcher-resident supervisor could never see what the workers
    # registered.
    ckpt_state = Path(state_root) / "last_checkpoint.json"
    os.environ["TORCHMPI_TPU_CHECKPOINT_STATE"] = str(ckpt_state)
    live_agg = _start_live_aggregator(args, telemetry_dir)

    def spawn_locked(addr: str) -> None:
        rank = next_rank[0]
        next_rank[0] += 1
        env = _worker_env(args, rank, restart)
        env["TORCHMPI_TPU_ELASTIC"] = addr
        env["TORCHMPI_TPU_ELASTIC_RANK"] = str(rank)
        env["TORCHMPI_TPU_CHECKPOINT_STATE"] = str(ckpt_state)
        if rank >= args.nproc:
            # spawned by an operator grow INTO a running job: the worker
            # must attach to the live membership, not wait for formation
            env["TORCHMPI_TPU_ELASTIC_JOINER"] = "1"
        if telemetry_dir is not None:
            tname = (
                f"telemetry_rank_{rank}.json" if restart == 0
                else f"telemetry_rank_{rank}.restart{restart}.json"
            )
            env["TORCHMPI_TPU_TELEMETRY"] = "1"
            env["TORCHMPI_TPU_TELEMETRY_DUMP"] = str(telemetry_dir / tname)
        if live_agg is not None:
            # elastic workers piggyback their live frames on the
            # membership heartbeat instead of opening another socket;
            # the coordinator's on_telemetry hook feeds the aggregator
            env["TORCHMPI_TPU_TELEMETRY"] = "1"
            env["TORCHMPI_TPU_TELEMETRY_LIVE_VIA"] = "heartbeat"
        if log_dir is not None:
            out = open(log_dir / f"rank_{rank}.log", "w")
            logs.append(out)
            proc = subprocess.Popen(
                target + extra, env=env, stdout=out,
                stderr=subprocess.STDOUT,
            )
        else:
            proc = subprocess.Popen(
                target + extra, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
            reader = threading.Thread(
                target=_stream, args=(proc, rank), daemon=True
            )
            reader.start()
            readers.append(reader)
        procs[rank] = proc

    coord_box = {}

    def on_grow():
        with lock:
            print("[launch] elastic grow: spawning one more worker",
                  file=sys.stderr)
            spawn_locked(coord_box["addr"])

    coord = ElasticCoordinator(
        on_grow=on_grow,
        on_telemetry=live_agg.ingest if live_agg is not None else None,
    )
    coord_box["addr"] = f"{coord.address[0]}:{coord.address[1]}"
    print(f"[launch] elastic coordinator at {coord_box['addr']}",
          file=sys.stderr)
    if args.elastic_addr_file:
        tmp = Path(args.elastic_addr_file).with_suffix(".tmp")
        tmp.parent.mkdir(parents=True, exist_ok=True)
        tmp.write_text(coord_box["addr"])
        os.replace(tmp, args.elastic_addr_file)

    rollback_box: dict = {}
    sup_stop = threading.Event()
    sup_thread = None
    if args.supervise:
        from . import constants
        from .supervise import RecoverySupervisor
        from .supervise import checkpoints as _ckpts

        class _Actuator:
            """The supervisor's levers over THIS launcher's job."""

            def evict(self, ranks, reason):
                with lock:
                    live = [r for r, p in procs.items()
                            if p.poll() is None]
                doomed = [r for r in ranks if r in live]
                if doomed and len(live) - len(doomed) < 1:
                    # cannot evict below 1 (the coordinator's own rule):
                    # a FAILED attempt — the bounded retries escalate to
                    # rollback instead of beheading the job
                    return False
                for r in ranks:
                    with lock:
                        p = procs.get(r)
                    if p is not None and p.poll() is None:
                        # SIGKILL, not SIGTERM: a wedged worker (the
                        # hang verdict) won't honor polite signals, and
                        # membership eviction follows from the silence
                        # (heartbeat sweep -> epoch bump -> live shrink)
                        p.kill()
                    # a deliberately evicted rank leaves the fleet view:
                    # the verdict must stop charging the job with it
                    live_agg.mark_evicted(r)
                return True

            def grow(self, reason):
                on_grow()
                return True

            def rollback(self, reason):
                if restart >= args.max_restarts:
                    # no restart budget left: killing the world would be
                    # a job death, not a rollback. Refuse (a counted
                    # FAILED attempt, journaled and bounded) — the
                    # survivors keep limping, which beats nothing.
                    print(
                        f"[supervise] rollback ({reason}) REFUSED: no "
                        "restart budget (give the job --max-restarts)",
                        file=sys.stderr,
                    )
                    return False
                rollback_box["reason"] = reason
                print(
                    f"[supervise] rollback ({reason}): killing the "
                    f"world — {_ckpts.describe_last()}",
                    file=sys.stderr,
                )
                with lock:
                    victims = list(procs.values())
                for p in victims:
                    if p.poll() is None:
                        p.kill()
                return True

        def _print_action(entry):
            print(
                "[supervise] action={action} verdict={verdict} "
                "ranks={ranks} windows={windows} attempt={attempt} "
                "result={result}".format(**entry),
                file=sys.stderr,
            )

        sup = RecoverySupervisor(
            _Actuator(), dry_run=args.supervise_dry_run,
            on_action=_print_action,
        )
        live_agg.attach_supervisor(sup)
        sup_interval = float(constants.get("telemetry_live_interval_s"))

        def _sup_loop():
            warned = False
            while not sup_stop.wait(sup_interval):
                try:
                    sup.observe(live_agg.evaluate())
                except Exception as e:  # noqa: BLE001 - one bad window
                    # must not end supervision, but a PERSISTENTLY
                    # broken sensor must not fail silent either
                    if not warned:
                        warned = True
                        print(
                            f"[supervise] verdict evaluation failed: "
                            f"{e!r} (supervision degraded; further "
                            "failures suppressed)",
                            file=sys.stderr,
                        )
        sup_thread = threading.Thread(
            target=_sup_loop, name="tm-supervisor", daemon=True
        )
        sup_thread.start()
        print(
            "[launch] recovery supervisor armed"
            + (" (dry-run)" if args.supervise_dry_run else "")
            + f" — actions at http://127.0.0.1:{live_agg.http_port}"
            "/actions",
            file=sys.stderr,
        )
        if not args.max_restarts and not args.supervise_dry_run:
            print(
                "[launch] note: --supervise without --max-restarts "
                "has no rollback budget — the rollback rung will "
                "refuse to fire (evict/quarantine still act)",
                file=sys.stderr,
            )

    with lock:
        for _ in range(args.nproc):
            spawn_locked(coord_box["addr"])

    rc = 0
    last_code = 0
    try:
        while True:
            with lock:
                live = {r: p for r, p in procs.items() if p.poll() is None}
                done = {r: p for r, p in procs.items() if p.poll() is not None}
                for r in done:
                    procs.pop(r, None)
            for r, p in sorted(done.items()):
                code = p.returncode
                last_code = 128 - code if code < 0 else code
                level = "exited" if code == 0 else "DIED"
                print(
                    f"[launch] elastic rank {r} {level} with {code}; "
                    f"{len(live)} worker(s) remain — continuing "
                    "(live elasticity: survivors reshard)",
                    file=sys.stderr,
                )
            if not live:
                rc = last_code
                break
            try:
                next(iter(live.values())).wait(timeout=0.2)
            except subprocess.TimeoutExpired:
                pass
    except KeyboardInterrupt:
        rc = 130
        with lock:
            remaining = list(procs.values())
        for p in remaining:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in remaining:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
    finally:
        sup_stop.set()
        if sup_thread is not None:
            sup_thread.join(timeout=5)
        coord.close()
        for reader in readers:
            reader.join(timeout=5)
        for f in logs:
            f.close()
        _close_live_aggregator(live_agg, telemetry_dir)
    if rollback_box.get("reason") and rc == 0:
        # every worker exited 0 despite a rollback kill (a race on the
        # way down): the attempt must still read as failed so the
        # restart loop relaunches from the checkpoint
        rc = 1
    return rc


def _run_world(args, target, extra, restart: int) -> int:
    """Spawn the full world once and wait for it (one restart attempt)."""
    # Restart attempts need a coordinator port the failed attempt's
    # lingering socket cannot shadow. Single-node relaunches pick a
    # fresh free port; multi-node relaunches cannot communicate a fresh
    # choice, so every node derives the SAME next port by arithmetic:
    # attempt k = --coordinator's port + k (reserve the range).
    if args.coordinator and args.nnodes > 1 and restart:
        host, _, port = args.coordinator.rpartition(":")
        coordinator = f"{host}:{int(port) + restart}"
    else:
        coordinator = (
            args.coordinator if restart == 0 and args.coordinator else None
        ) or f"localhost:{_free_port()}"
    world = args.nnodes * args.nproc
    base = args.node_rank * args.nproc
    procs: List[subprocess.Popen] = []
    logs = []
    readers: List[threading.Thread] = []
    log_dir = Path(args.log_dir) if args.log_dir else None
    if log_dir is not None:
        log_dir.mkdir(parents=True, exist_ok=True)
    telemetry_dir = Path(args.telemetry_dir) if args.telemetry_dir else None
    if telemetry_dir is not None:
        telemetry_dir.mkdir(parents=True, exist_ok=True)
        # clear liveness/hang artifacts from a previous attempt or a
        # reused dir: a SIGKILL'd rank never retracts its heartbeat, and
        # a leftover hang report (or live-plane dead-rank marker) would
        # read as THIS run's diagnosis
        for pattern in ("heartbeat_rank_*.json", "hang_rank_*.json",
                        "dead_rank_*.json"):
            for stale in telemetry_dir.glob(pattern):
                try:
                    stale.unlink()
                except OSError:
                    pass
    live_agg = _start_live_aggregator(args, telemetry_dir)
    for i in range(args.nproc):
        rank = base + i
        # _worker_env: PROCESS_ID/RESTART_COUNT, --set-constant knob
        # overrides (applied by start() pre-bootstrap), watchdog arming,
        # and the virtual-CPU-mesh flags
        env = _worker_env(args, rank, restart)
        env["TORCHMPI_TPU_COORDINATOR"] = coordinator
        env["TORCHMPI_TPU_NUM_PROCESSES"] = str(world)
        if telemetry_dir is not None:
            # the env var both enables telemetry in the rank and registers
            # its atexit dump (torchmpi_tpu.telemetry import-time hook);
            # restart attempts keep distinct files like the logs do
            tname = (
                f"telemetry_rank_{rank}.json" if restart == 0
                else f"telemetry_rank_{rank}.restart{restart}.json"
            )
            env["TORCHMPI_TPU_TELEMETRY"] = "1"
            env["TORCHMPI_TPU_TELEMETRY_DUMP"] = str(telemetry_dir / tname)
        if live_agg is not None:
            # arm the per-rank live exporter (telemetry import-time
            # hook) streaming to the launcher's aggregator
            env["TORCHMPI_TPU_TELEMETRY"] = "1"
            env["TORCHMPI_TPU_TELEMETRY_LIVE"] = (
                f"127.0.0.1:{live_agg.ingest_port}"
            )
        if log_dir is not None:
            # restart attempts keep distinct logs: the failed attempt's
            # tail is the evidence worth reading
            name = (
                f"rank_{rank}.log" if restart == 0
                else f"rank_{rank}.restart{restart}.log"
            )
            out = open(log_dir / name, "w")
            logs.append(out)
            proc = subprocess.Popen(
                target + extra, env=env, stdout=out,
                stderr=subprocess.STDOUT,
            )
        else:
            proc = subprocess.Popen(
                target + extra, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
            reader = threading.Thread(
                target=_stream, args=(proc, rank), daemon=True
            )
            reader.start()
            readers.append(reader)
        procs.append(proc)

    # one rank failing kills the rest (the reference needed manual pkill)
    rc = 0
    try:
        remaining = set(range(args.nproc))
        while remaining and rc == 0:
            for i in [i for i in remaining if procs[i].poll() is not None]:
                remaining.discard(i)
                code = procs[i].returncode
                if code != 0 and rc == 0:
                    # signal deaths (segfault/OOM-kill) surface as the
                    # conventional 128+signum, not Popen's negative code
                    # (sys.exit(-9) would report 247)
                    rc = 128 - code if code < 0 else code
                    print(
                        f"[launch] rank {base + i} exited with {code}; "
                        "terminating remaining ranks",
                        file=sys.stderr,
                    )
            if rc == 0 and remaining:
                try:
                    procs[sorted(remaining)[0]].wait(timeout=0.2)
                except subprocess.TimeoutExpired:
                    pass
    except KeyboardInterrupt:
        rc = rc or 130
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        # drain the stream readers before returning: daemon threads die
        # with the interpreter, and the undrained tail of a failed rank's
        # output is exactly the part that explains the failure
        for reader in readers:
            reader.join(timeout=5)
        for f in logs:
            f.close()
        _close_live_aggregator(live_agg, telemetry_dir)
    return rc


if __name__ == "__main__":
    sys.exit(main())
