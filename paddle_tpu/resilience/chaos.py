"""Deterministic fault injection (env-flag controlled).

Every resilience path in this repo is testable on the CPU mesh because the
faults it defends against can be INJECTED deterministically:

    PADDLE_TPU_CHAOS="sigterm_at_step:7;nan_at_step:3"

Spec grammar: `;`-separated `name[:int[:float]]` entries —

    sigterm_at_step:K     deliver a real SIGTERM to this process at global
                          train step K (hapi Model.fit batch loop)
    nan_at_step:K         the compiled train step produces a NaN loss (and
                          NaN grads) at optimizer step K (jit/engine.py;
                          1-based like optimizer._step_count)
    hang_at_step:K:SECS   host-side sleep of SECS inside the compiled-step
                          dispatch of optimizer step K (exercises the step
                          watchdog; 1-based)
    oom:K                 the compiled-step dispatch of optimizer step K
                          (1-based) raises a synthetic RESOURCE_EXHAUSTED,
                          driving the real OOM-forensics path (memprof
                          catch -> oom journal event -> crash bundle with
                          memory.json) without exhausting any HBM
    torn_write:K          the K-th checkpoint blob written by this process
                          (checkpoint/store.py; 1-based) is torn: half its
                          bytes reach disk, then the process is SIGKILLed —
                          a deterministic power-loss mid-save
    bitflip_ckpt:K        one bit of the K-th checkpoint blob is flipped
                          AFTER its checksum is recorded in the manifest —
                          deterministic bit rot the verified loader must
                          detect, quarantine and fall back from
    kill_rank:R[:K]       rank R SIGKILLs itself at train step K (default
                          2) — an abrupt peer death the launcher's gang
                          restart must recover (distributed/launch.py)
    hang_rank:R[:K[:S]]   rank R stops making progress at step K (default
                          2): a host-side sleep of S seconds (default
                          3600) with the heartbeat stopped, so the hang
                          detector must notice, kill it, and gang-restart
    dead_rank:R[:K]       rank R SIGKILLs itself at step K (default 2) in
                          EVERY restart round — a permanently-lost host
                          that never comes back, so the launcher's
                          shrink-to-fit must abandon it and respawn the
                          gang at a smaller world (docs/RESILIENCE.md
                          "Elastic topology changes")

kill_rank / hang_rank fire only in restart round 0 (the launcher exports
PADDLE_TPU_RESTART_ROUND to respawned workers), so a gang-restarted job
resumes instead of re-killing itself into an infinite restart loop.
dead_rank deliberately BYPASSES that gate — permanence is the fault being
injected — and relies on the launcher's shrink respawning a world that no
longer contains rank R.

Injection sites poll this module; with the env var unset every hook is a
cheap no-op. Counters are in-process (each injected fault fires its exact
configured schedule within one process lifetime).

Reference analogue: the fault-injection envs in the reference's elastic
tests (test_fleet_elastic_manager.py fakes etcd faults) — here promoted to
a first-class, grep-able harness.

Pure stdlib: nothing here imports jax or the rest of the package.
"""
from __future__ import annotations

import os
import signal
import sys
import time
from typing import Dict, Optional, Tuple

ENV_VAR = "PADDLE_TPU_CHAOS"

_spec_cache: Optional[Tuple[str, Dict[str, Tuple[float, ...]]]] = None
_counts: Dict[str, int] = {}


def _parse(spec: str) -> Dict[str, Tuple[float, ...]]:
    out: Dict[str, Tuple[float, ...]] = {}
    for entry in spec.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        try:
            out[parts[0]] = tuple(float(p) for p in parts[1:])
        except ValueError:
            raise ValueError("bad %s entry %r (want name[:num[:num]])"
                             % (ENV_VAR, entry))
    return out


def _active() -> Dict[str, Tuple[float, ...]]:
    """Parsed spec for the CURRENT env value (re-read on change so tests
    can flip the env or call configure() mid-process)."""
    global _spec_cache
    raw = os.environ.get(ENV_VAR, "")
    if _spec_cache is None or _spec_cache[0] != raw:
        _spec_cache = (raw, _parse(raw))
        _counts.clear()
    return _spec_cache[1]


def configure(spec: str) -> None:
    """Programmatic injection (tests): equivalent to setting the env var."""
    if spec:
        os.environ[ENV_VAR] = spec
    else:
        os.environ.pop(ENV_VAR, None)
    _active()


def reset() -> None:
    configure("")


def enabled() -> bool:
    return bool(_active())


def get(name: str) -> Optional[Tuple[float, ...]]:
    return _active().get(name)


def nan_at_step() -> Optional[int]:
    """Optimizer-step index at which the train step must produce NaN, or
    None. Read once at trace time by the jit engine (static)."""
    args = get("nan_at_step")
    return int(args[0]) if args else None


def step_hook(step: int) -> None:
    """Per-train-step host hook: fires the sigterm injection. Call with
    the GLOBAL step index (0-based batch counter in Model.fit)."""
    args = get("sigterm_at_step")
    if args and int(args[0]) == step and not _counts.get("sigterm"):
        _counts["sigterm"] = 1
        os.kill(os.getpid(), signal.SIGTERM)


def torn_write_blob() -> bool:
    """True when the CURRENT checkpoint blob write must be torn
    (torn_write:K, 1-based blob counter per process lifetime). The store
    responds by persisting half the payload and SIGKILLing the process."""
    args = get("torn_write")
    if not args:
        return False
    n = _counts.get("torn_write", 0) + 1
    _counts["torn_write"] = n
    return n == int(args[0])


def bitflip_blob() -> bool:
    """True when the current checkpoint blob must have one bit flipped
    after its checksum is recorded (bitflip_ckpt:K, 1-based)."""
    args = get("bitflip_ckpt")
    if not args:
        return False
    n = _counts.get("bitflip_ckpt", 0) + 1
    _counts["bitflip_ckpt"] = n
    return n == int(args[0])


def _rank_fault(name: str, rank: int, step: int) -> Optional[Tuple[float, ...]]:
    args = get(name)
    if not args or int(args[0]) != rank:
        return None
    at = int(args[1]) if len(args) > 1 else 2
    if step != at or _counts.get(name):
        return None
    _counts[name] = 1
    return args


def _flight_dump(reason: str, step: int) -> None:
    """Crash-bundle the flight ring BEFORE an injected fault lands.
    SIGKILL is uncatchable and a hang never returns, so the pre-mortem
    dump is the only one there will ever be — exactly what a real
    external SIGKILL denies us, which is why the drill writes it here.
    sys.modules only (chaos stays pure-stdlib; no package, no dump)."""
    flight = sys.modules.get("paddle_tpu.observability.flight")
    if flight is None:
        return
    try:
        flight.dump_crash_bundle(reason, last_step=step)
    except Exception:
        pass


def rank_fault_hook(rank: int, step: int) -> None:
    """Per-train-step host hook for rank-targeted gang faults
    (kill_rank:R[:K], hang_rank:R[:K[:S]]). Call with this process's rank
    and the global step BEFORE the heartbeat tick, so a hung rank's last
    heartbeat is strictly older than its surviving peers'. kill_rank /
    hang_rank are no-ops outside restart round 0; dead_rank fires in
    every round — see the module docstring."""
    if _rank_fault("dead_rank", rank, step) is not None:
        _flight_dump("chaos_dead", step)
        os.kill(os.getpid(), signal.SIGKILL)
    try:
        if int(os.environ.get("PADDLE_TPU_RESTART_ROUND", "0") or 0) > 0:
            return
    except ValueError:
        return
    if _rank_fault("kill_rank", rank, step) is not None:
        _flight_dump("chaos_kill", step)
        os.kill(os.getpid(), signal.SIGKILL)
    args = _rank_fault("hang_rank", rank, step)
    if args is not None:
        _flight_dump("chaos_hang", step)
        time.sleep(args[2] if len(args) > 2 else 3600.0)


def hang_before_dispatch(step: int) -> None:
    """Engine hook: host-side sleep inside the compiled-step dispatch of
    optimizer step `step` (1-based), under the step watchdog's scope."""
    args = get("hang_at_step")
    if args and int(args[0]) == step and not _counts.get("hang_%d" % step):
        _counts["hang_%d" % step] = 1
        time.sleep(args[1] if len(args) > 1 else 5.0)


def oom_at_dispatch(step: int) -> None:
    """Engine hook: raise a synthetic RESOURCE_EXHAUSTED from the
    compiled-step dispatch of optimizer step `step` (1-based, once per
    process). The message matches the XLA runtime's spelling so the
    engines' real OOM catch (observability/memprof.py) fires, proving
    the memory.json bundle path end-to-end on the CPU mesh."""
    args = get("oom")
    if args and int(args[0]) == step and not _counts.get("oom_%d" % step):
        _counts["oom_%d" % step] = 1
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: injected by %s=oom:%d — synthetic HBM "
            "exhaustion (chaos drill)" % (ENV_VAR, step))
