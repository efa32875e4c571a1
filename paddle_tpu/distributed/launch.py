"""Distributed launcher CLI — `python -m paddle_tpu.distributed.launch`.

TPU-native equivalent of the reference's fleetrun / launch_collective
(/root/reference/python/paddle/distributed/fleet/launch.py:276-347,451):
build per-rank env (PADDLE_TRAINER_ID / PADDLE_TRAINER_ENDPOINTS /
FLAGS_selected_gpus), spawn local workers, watch, tear down on failure.

On TPU pods the launcher starts ONE controller process per HOST (not per
chip): a chip belongs to one process at a time, and the mesh is built from
everything jax.devices() reports (fleet/topology.py). Rank 0's address
doubles as the jax.distributed coordinator — the DCN replacement for the
reference's gen_nccl_id TCP handshake. `--nproc_per_node > 1` (the
reference's per-GPU mode) is a TEST HARNESS: several controllers cannot
share a host's chips, so every worker is put on its own virtual CPU device
(JAX_PLATFORMS=cpu) and the launcher says so at WARNING.
"""
from __future__ import annotations

import argparse
import logging
import os
import signal
import socket
import subprocess
import sys
import time
import uuid

from ..observability import journal as run_journal
from ..observability import metrics
from ..resilience import health

logger = logging.getLogger("paddle_tpu.launch")


def _aggregate(log_dir: str, cause: str) -> None:
    """Merge per-rank journals/heartbeats/crash bundles into
    timeline.jsonl + metrics-rollup.json (observability/aggregate.py).
    Called at exit AND after every gang restart, so the run-level view
    of round N survives even when the launcher itself is later killed.
    Best-effort: teardown paths must not gain new failure modes."""
    try:
        from ..observability import aggregate
        res = aggregate.aggregate_run(log_dir, cause=cause)
        if res:
            logger.info("telemetry aggregated (%s): %d events -> %s",
                        cause, res["events"], res["timeline"])
    except Exception as e:
        logger.warning("telemetry aggregation failed: %s", e)


def _parse_mesh_axes(spec):
    """PADDLE_TPU_MESH_AXES="dp:2,mp:2" -> (("dp", 2), ("mp", 2)). The
    launcher has no sharding plan of its own; a hybrid job exports its
    structural degrees here so shrink-to-fit never lands on a world size
    the mesh cannot factorize. Malformed specs return None (pure-dp)."""
    axes = []
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        name, _, deg = part.replace("=", ":").partition(":")
        try:
            axes.append((name.strip(), int(deg)))
        except ValueError:
            return None
    return tuple(axes) or None


def _shrink_target(cur_world: int) -> int:
    """Largest feasible world <= cur_world - 1 for a shrink-to-fit gang
    restart (planner.largest_feasible_world; non-dp mesh axes from
    PADDLE_TPU_MESH_AXES must survive intact). Returns 0 when the job
    cannot shrink — below one full model replica, or already world 1."""
    mesh_axes = _parse_mesh_axes(os.environ.get("PADDLE_TPU_MESH_AXES"))
    try:
        from .auto_parallel.planner import largest_feasible_world
    except Exception:
        # the planner pulls in jax; the supervisor can live without it
        structural = 1
        for name, deg in (mesh_axes or ()):
            if name != "dp":
                structural *= int(deg)
        n_max = cur_world - 1
        return (n_max // structural) * structural \
            if 0 < structural <= n_max else 0
    return largest_feasible_world(cur_world - 1, mesh_axes)


class _Worker:
    """One spawned worker process and its bookkeeping."""

    __slots__ = ("rank", "local_rank", "proc", "out", "spawn_t")

    def __init__(self, rank, local_rank, proc, out, spawn_t):
        self.rank = rank
        self.local_rank = local_rank
        self.proc = proc
        self.out = out
        self.spawn_t = spawn_t


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")))
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")))
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes on this host (hosts, not chips: "
                        "one SPMD controller drives all local chips; >1 is "
                        "a test harness that puts every worker on the CPU)")
    p.add_argument("--master", default=None,
                   help="coordinator host:port (defaults to a local port)")
    p.add_argument("--ips", default=None, help="comma list of host ips")
    p.add_argument("--log_dir", default=None)
    p.add_argument("--run_mode", default="collective")
    p.add_argument("--devices", "--gpus", "--xpus", dest="devices",
                   default=None)
    p.add_argument("--max_restarts", type=int,
                   default=int(os.environ.get("PADDLE_LAUNCH_MAX_RESTARTS",
                                              "0")),
                   help="total failed-worker respawns before the launch "
                        "gives up (reference: the elastic manager's "
                        "restart budget); 0 = fail fast. In a world > 1 "
                        "collective job each restart is a GANG restart: "
                        "every local worker is torn down and respawned "
                        "together (docs/RESILIENCE.md)")
    p.add_argument("--hang_timeout_s", type=float,
                   default=float(os.environ.get("PADDLE_TPU_HANG_TIMEOUT_S",
                                                "0") or 0),
                   help="declare a worker HUNG (and kill + restart it) "
                        "when its heartbeat file under --log_dir goes "
                        "stale this long while the pid is alive; 0 = off. "
                        "Requires --log_dir; set it well above the "
                        "slowest legitimate step time")
    p.add_argument("--checkpoint_dir",
                   default=os.environ.get("PADDLE_TPU_CHECKPOINT_DIR"),
                   help="exported to workers as PADDLE_TPU_CHECKPOINT_DIR "
                        "(TrainEpochRange root); the launcher sweeps stale "
                        "commit droppings there before every (re)spawn so "
                        "a crashed worker's torn save never confuses the "
                        "resume scan (docs/CHECKPOINT.md)")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def launch_collective(args) -> int:
    nprocs = args.nproc_per_node
    world = args.nnodes * nprocs
    # sticky: a shrink can drop nprocs to 1 but the survivors still share
    # this host with the launcher and must keep their virtual CPU devices
    multiproc = nprocs > 1
    if multiproc:
        logger.warning(
            "--nproc_per_node=%d: one host's chips are driven by ONE "
            "process, so these workers do not get them — each runs on a "
            "virtual CPU device (JAX_PLATFORMS=%s). This mode is a test "
            "harness; to train on the chips use --nproc_per_node=1.",
            nprocs, os.environ.get("JAX_PLATFORMS", "cpu"))
    master = args.master or f"127.0.0.1:{_free_port()}"
    endpoints = ",".join(
        f"127.0.0.1:{_free_port()}" for _ in range(world))
    log_dir = args.log_dir
    journal_obj = prev_journal = None
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        # the launcher's own journal sits next to the per-rank worker ones
        # (workers write journal-rank<N>.jsonl into their telemetry_dir)
        journal_obj = run_journal.RunJournal(
            log_dir, filename="journal-launch.jsonl",
            rank=args.node_rank)
        prev_journal = run_journal.set_journal(journal_obj)
        journal_obj.emit("launch_start", nnodes=args.nnodes,
                         nproc_per_node=nprocs, world=world, master=master)

    def sweep_checkpoints():
        if not args.checkpoint_dir:
            return
        try:
            from ..checkpoint.engine import sweep_stale
            for sub in [args.checkpoint_dir] + [
                    os.path.join(args.checkpoint_dir, n)
                    for n in sorted(os.listdir(args.checkpoint_dir))
                    if os.path.isdir(os.path.join(args.checkpoint_dir, n))]:
                removed = sweep_stale(sub)
                if removed:
                    logger.info("swept stale checkpoint dirs in %s: %s",
                                sub, removed)
        except OSError as e:
            logger.warning("checkpoint sweep failed: %s", e)

    grace_s = float(os.environ.get("PADDLE_TPU_GANG_GRACE_S", "10") or 10)
    _trace_id = uuid.uuid4().hex[:12]

    # live fleet plane (observability/httpd.py): with $PADDLE_TPU_HTTP_PORT
    # set the launcher serves a fleet-level /statusz that fans out to the
    # per-rank endpoints (workers are re-pointed at port 0 + discovery
    # files below); unset, no socket anywhere — the parity contract.
    fleet_http = os.environ.get("PADDLE_TPU_HTTP_PORT")
    fleet_srv = None
    if log_dir and fleet_http not in (None, ""):
        from ..observability import httpd

        def _workers_alive():
            live = sum(1 for w in procs if w.proc.poll() is None)
            return live > 0, "%d/%d workers alive" % (live, len(procs))

        def _launch_status():
            return {"world": world, "nnodes": args.nnodes,
                    "restarts": restarts, "rounds": rounds,
                    "shrinks": shrinks,
                    "workers": [{"rank": w.rank, "pid": w.proc.pid,
                                 "alive": w.proc.poll() is None}
                                for w in procs]}

        try:
            fleet_srv = httpd.TelemetryServer(
                port=int(fleet_http), rank=args.node_rank,
                endpoint_dir=None, fleet_dir=log_dir).start()
            httpd.register_probe("workers", _workers_alive)
            httpd.register_status("launch", _launch_status)
            logger.info("fleet telemetry at %s (/statusz fans out to "
                        "endpoint-rank*.json under %s)",
                        fleet_srv.url, log_dir)
        except (ValueError, OSError) as e:
            logger.warning("fleet telemetry server failed to start: %s", e)
            fleet_srv = None

    def spawn(local_rank, respawn=False, restart_round=0):
        rank = args.node_rank * nprocs + local_rank
        sweep_checkpoints()
        env = dict(os.environ)
        if args.checkpoint_dir:
            env["PADDLE_TPU_CHECKPOINT_DIR"] = args.checkpoint_dir
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT": endpoints.split(",")[rank],
            "PADDLE_RANK_IN_NODE": str(local_rank),
            # chaos rank faults fire only in round 0 (resilience/chaos.py),
            # so an injected kill/hang cannot loop the restart budget away
            "PADDLE_TPU_RESTART_ROUND": str(restart_round),
        })
        if world > 1:
            env["PADDLE_COORDINATOR_ADDRESS"] = master
        if log_dir:
            # workers heartbeat into the log dir; the watch loop's hang
            # detector reads the files back (resilience/health.py)
            env["PADDLE_TPU_HEARTBEAT_DIR"] = log_dir
            # workers journal + crash-bundle into the same dir (setdefault:
            # an operator-set telemetry home wins over the launcher's)
            env.setdefault("PADDLE_TPU_TELEMETRY_DIR", log_dir)
            env.setdefault("PADDLE_TPU_FLIGHT_DIR", log_dir)
            # one trace id for every rank and restart round, so the span
            # events of a whole gang correlate (observability/spans.py);
            # setdefault survives into respawns via os.environ copies
            env.setdefault("PADDLE_TPU_TRACE_ID", _trace_id)
            try:  # a dead incarnation's heartbeat must not damn the new one
                os.unlink(health.heartbeat_path(log_dir, rank))
            except OSError:
                pass
        if env.get("PADDLE_TPU_HTTP_PORT"):
            # the operator's fixed port belongs to the launcher's fleet
            # endpoint; N workers inheriting it would collide, so each
            # worker binds an ephemeral port and publishes it through an
            # endpoint-rank<N>.json discovery file in its telemetry dir
            env["PADDLE_TPU_HTTP_PORT"] = "0"
            if log_dir:
                try:
                    from ..observability import httpd as _httpd
                    os.unlink(_httpd.endpoint_path(log_dir, rank))
                except (ImportError, OSError):
                    pass
        if multiproc:
            # Several controllers on one host: give each ONE virtual CPU
            # device (a user-set JAX_PLATFORMS is honored, not overridden)
            from ..framework.platform import with_host_device_count
            env.setdefault("JAX_PLATFORMS", "cpu")
            env["XLA_FLAGS"] = with_host_device_count(
                env.get("XLA_FLAGS", ""), 1)
        cmd = [sys.executable, "-u", args.training_script,
               *args.training_script_args]
        out = None
        if log_dir:
            out = open(os.path.join(log_dir, f"workerlog.{rank}"),
                       "a" if respawn else "w")
            if respawn:
                out.write(f"--- respawn {restart_round} ---\n")
                out.flush()
        proc = subprocess.Popen(cmd, env=env, stdout=out,
                                stderr=subprocess.STDOUT if out else None)
        logger.info("spawned worker rank %d pid %d%s", rank, proc.pid,
                    " (respawn)" if respawn else "")
        run_journal.emit("worker_spawn", rank=rank, pid=proc.pid,
                         respawn=bool(respawn))
        return _Worker(rank=rank, local_rank=local_rank, proc=proc,
                       out=out, spawn_t=time.time())

    def close_logs():
        for w in procs:
            if w.out and not w.out.closed:
                w.out.close()

    def kill_with_grace(workers):
        """SIGTERM first (PreemptionGuard flushes its grace-window
        checkpoint), escalate to SIGKILL after the gang grace budget."""
        for w in workers:
            if w.proc.poll() is None:
                w.proc.send_signal(signal.SIGTERM)
        deadline = time.time() + grace_s
        for w in workers:
            try:
                w.proc.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                w.proc.kill()
                w.proc.wait()

    def find_hung_worker():
        """The stalest live rank whose heartbeat outaged the timeout, or
        None. A rank with NO heartbeat yet is never hung — a wedge before
        the first tick is the bootstrap deadline's problem."""
        if args.hang_timeout_s <= 0 or not log_dir:
            return None
        hung, worst = None, args.hang_timeout_s
        now = time.time()
        for w in procs:
            if w.proc.poll() is not None:
                continue
            hb = health.heartbeat_path(log_dir, w.rank)
            stale = health.stale_seconds(hb, now)
            # only heartbeats from THIS incarnation count (mtime after
            # spawn); spawn() also unlinks the previous one defensively
            if stale is None or now - stale < w.spawn_t:
                continue
            if stale > worst:
                hung, worst = w, stale
        return (hung, worst) if hung is not None else None

    procs = [spawn(lr) for lr in range(nprocs)]

    # $PADDLE_TPU_AGG_INTERVAL_S > 0: re-run the cross-rank aggregation
    # every interval while the gang is healthy, so timeline.jsonl and
    # metrics-rollup.json (what fleet /statusz attaches) track a LIVE
    # run instead of only materializing at exit/restart boundaries
    try:
        from ..observability import aggregate as _agg_mod
        agg_tick = _agg_mod.PeriodicAggregator(log_dir)
    except Exception:
        agg_tick = None

    # watch loop (reference: fleet/launch.py:276-347) with a bounded
    # restart budget (reference: elastic manager). world == 1: a crashed
    # worker is respawned individually. world > 1: any worker death —
    # crash OR detected hang — triggers a GANG restart, because the
    # surviving ranks of a collective job are blocked on the dead peer:
    # graceful teardown of every local worker, stale-checkpoint sweep,
    # full respawn; workers auto-resume from last-good (docs/CHECKPOINT.md)
    max_restarts = max(0, args.max_restarts)
    restarts = 0    # budget-charged same-size respawn cycles
    rounds = 0      # ALL respawn cycles (restarts + shrinks) — what
                    # PADDLE_TPU_RESTART_ROUND and log separators count
    shrinks = 0
    # per-rank crash attribution: a streak of consecutive failures of the
    # SAME rank is the shrink-to-fit trigger (a healthy gang restart gives
    # every rank a fresh chance; a rank that dies again immediately is
    # gone for good — docs/RESILIENCE.md "Elastic topology changes")
    last_failed_rank = None
    streak = 0
    try:
        shrink_after = int(os.environ.get("PADDLE_TPU_SHRINK_AFTER", "2"))
    except ValueError:
        shrink_after = 2
    backoff = None
    if max_restarts:
        from ..resilience import RetryPolicy
        backoff = RetryPolicy(max_tries=max_restarts + 1, base_delay=1.0,
                              max_delay=30.0)
    rc = 0
    try:
        while True:
            failed = None          # (worker, cause, exit_code)
            alive = False
            for w in procs:
                code = w.proc.poll()
                if code is None:
                    alive = True
                elif code != 0:
                    run_journal.emit("worker_exit", rank=w.rank,
                                     local_rank=w.local_rank,
                                     pid=w.proc.pid, code=code)
                    failed = (w, "crash", code)
                    break
            if failed is None:
                hung = find_hung_worker()
                if hung is not None:
                    w, stale = hung
                    hb = health.read_heartbeat(
                        health.heartbeat_path(log_dir, w.rank)) or {}
                    logger.warning(
                        "worker rank %d pid %d HUNG: heartbeat stale "
                        "%.1fs > %.1fs (last step %s) — killing",
                        w.rank, w.proc.pid, stale, args.hang_timeout_s,
                        hb.get("step"))
                    metrics.counter(
                        "pt_worker_hangs_total",
                        "Live workers killed for a stale heartbeat").inc()
                    run_journal.emit("worker_hang", rank=w.rank,
                                     local_rank=w.local_rank, pid=w.proc.pid,
                                     stale_s=round(stale, 3),
                                     timeout_s=args.hang_timeout_s,
                                     last_step=hb.get("step"))
                    kill_with_grace([w])
                    failed = (w, "hang", None)
            if failed is None:
                if not alive:
                    break          # every worker exited 0
                time.sleep(0.5)
                if agg_tick is not None:
                    agg_tick.maybe()
                continue

            w, cause, code = failed
            streak = streak + 1 if w.rank == last_failed_rank else 1
            last_failed_rank = w.rank

            # shrink-to-fit sits BEFORE the budget check and does not
            # charge it: abandoning a permanently-dead rank is progress,
            # not another spin of the same failure. Single-node only —
            # multi-node membership changes need a coordinator-side
            # re-form this launcher cannot drive alone.
            new_world = 0
            if (world > 1 and args.nnodes == 1 and shrink_after > 0
                    and streak >= shrink_after):
                new_world = _shrink_target(world)
            if new_world >= 1:
                shrinks += 1
                rounds += 1
                logger.warning(
                    "worker rank %d %s %d times in a row — SHRINKING "
                    "world %d -> %d (gang respawn without the dead rank)",
                    w.rank, cause, streak, world, new_world)
                metrics.counter(
                    "pt_gang_shrinks_total",
                    "Shrink-to-fit gang restarts at a smaller world "
                    "size").inc()
                run_journal.emit("gang_shrink", failed_rank=w.rank,
                                 cause=cause, code=code, streak=streak,
                                 from_world=world, to_world=new_world,
                                 round=rounds)
                kill_with_grace(procs)
                close_logs()
                if log_dir:
                    _aggregate(log_dir, "gang_shrink")
                world = new_world
                nprocs = world      # single-node: every rank is local
                endpoints = ",".join(
                    f"127.0.0.1:{_free_port()}" for _ in range(world))
                if world > 1:
                    master = f"127.0.0.1:{_free_port()}"
                last_failed_rank, streak = None, 0
                procs = [spawn(lr, respawn=True, restart_round=rounds)
                         for lr in range(nprocs)]
                continue

            if restarts >= max_restarts:
                rc = code if code else 1
                raise RuntimeError(
                    f"worker rank {w.rank} pid {w.proc.pid} "
                    f"{'hung' if cause == 'hang' else f'exited with code {code}'}"
                    f" — restart budget ({max_restarts}) exhausted")
            restarts += 1
            rounds += 1
            delay = backoff.backoff(restarts)
            if world > 1:
                logger.warning(
                    "worker rank %d %s — GANG restart %d/%d in %.1fs",
                    w.rank, cause, restarts, max_restarts, delay)
                metrics.counter(
                    "pt_gang_restarts_total",
                    "Whole-gang teardown+respawn cycles").inc()
                run_journal.emit("gang_restart", failed_rank=w.rank,
                                 cause=cause, code=code, restart=restarts,
                                 max_restarts=max_restarts, world=world,
                                 round=rounds, delay_s=round(delay, 3))
                kill_with_grace(procs)
                close_logs()
                if log_dir:
                    _aggregate(log_dir, "gang_restart")
                time.sleep(delay)
                procs = [spawn(lr, respawn=True, restart_round=rounds)
                         for lr in range(nprocs)]
            else:
                logger.warning(
                    "worker pid %d (local rank %d) %s — restart %d/%d "
                    "in %.1fs", w.proc.pid, w.local_rank,
                    cause if cause == "hang" else f"exited with code {code}",
                    restarts, max_restarts, delay)
                metrics.counter("pt_worker_restarts_total",
                                "Failed workers respawned by the "
                                "launcher").inc()
                run_journal.emit("worker_restart", local_rank=w.local_rank,
                                 cause=cause, restart=restarts,
                                 max_restarts=max_restarts,
                                 delay_s=round(delay, 3))
                time.sleep(delay)
                if w.out:
                    w.out.close()
                procs[w.local_rank] = spawn(w.local_rank, respawn=True,
                                            restart_round=rounds)
    except (RuntimeError, KeyboardInterrupt) as e:
        kill_with_grace(procs)
        if isinstance(e, RuntimeError):
            logger.error("launch failed: %s", e)
            rc = rc or 1
    finally:
        close_logs()
        if fleet_srv is not None:
            try:
                from ..observability import httpd
                httpd.unregister_probe("workers")
                httpd.unregister_status("launch")
                fleet_srv.stop()
            except Exception as e:
                logger.warning("fleet telemetry shutdown failed: %s", e)
        if journal_obj is not None:
            # per-line flush puts launch_end on disk before aggregation
            # reads the journal files back
            journal_obj.emit("launch_end", rc=rc, restarts=restarts,
                             shrinks=shrinks, world=world)
        if log_dir:
            try:  # the gate and operators read the counters back from here
                metrics.REGISTRY.write_json(
                    os.path.join(log_dir, "metrics-launch.json"))
            except OSError as e:
                logger.warning("launch metrics snapshot failed: %s", e)
            _aggregate(log_dir, "exit")
        if journal_obj is not None:
            run_journal.set_journal(prev_journal)
            journal_obj.close()
    return rc


def main(argv=None) -> int:
    # human-readable console output, verbosity via PADDLE_TPU_LOG_LEVEL
    # (the journal, not the console, is the machine-readable record)
    logging.basicConfig(
        level=os.environ.get("PADDLE_TPU_LOG_LEVEL", "INFO").upper(),
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
        stream=sys.stderr)
    args = _parse_args(argv)
    return launch_collective(args)


if __name__ == "__main__":
    sys.exit(main())
