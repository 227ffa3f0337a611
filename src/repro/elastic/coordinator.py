"""Elastic multi-host supervisor (DESIGN.md §4b).

A :class:`Coordinator` turns the single-process trainer into a supervised,
resizable fleet: it spawns ``world_size`` worker subprocesses (every rank the
same ``python -m repro.launch.train`` entry with a ``--worker-id/--world-size/
--fleet-dir`` handshake), watches their liveness through heartbeat files with
a deadline derived from the straggler watchdog's per-step EMA, and applies the
exit-code-aware :class:`~repro.elastic.policy.RestartPolicy` to every exit:

* exit 75 (boundary drain) → relaunch immediately; the worker resumes from
  ``latest_valid()`` with nothing lost.
* crash / SIGKILL / heartbeat loss → SIGKILL (if wedged), then restart under
  exponential backoff with deterministic jitter, within a bounded per-rank
  restart budget.
* exit 76/77 (straggler / numerics escalation) → halt the fleet and surface
  the code — respawning does not fix a slow device or an exhausted guard.
* budget exhausted → **graceful degradation**: drain the survivors to the
  next GradES boundary checkpoint (SIGTERM → the chief's drain protocol),
  reform at ``world − 1``, resume.  A scheduled ``scale_up_at`` step restores
  the target width the same way, in reverse.

**Simulated multi-host.**  On CPU the fleet contracts the device runtime into
the chief (rank 0), whose ``XLA_FLAGS`` force ``world_size`` host-platform
devices — one per fleet worker — over which ``launch/mesh.py::make_dp_mesh``
lays a pure-DP ``("data",)`` mesh.  Scale-down is therefore a *real* mesh
reform: the relaunched chief re-derives batch shardings, the freeze-mask
``ReducePlan``, and the plan-independent moment/EF layouts from the boundary
checkpoint at the new data-parallel width, bit-identical to an uninterrupted
run at that width (``tests/test_elastic_fleet.py``).  Followers hold no
devices — they heartbeat and honor the drain protocol — so what this
simulation does *not* exercise is cross-host collective transport; everything
else (membership, liveness, restart policy, boundary-aligned resize, resume
bit-identity) is the real article.

Every elasticity path is chaos-testable through the deterministic fault layer:
``--inject-fault preempt@step[:grace_s]`` and ``worker_lost@step[:rank]``
(``robustness/faults.py``) fire here, keyed on the chief's heartbeat step,
with victims pure in ``(seed, step)``.  Recovery latency, restart counts, and
steps-lost-per-fault are recorded per event and summarized for
``BENCH_elastic.json`` (``benchmarks/bench_elastic.py``).
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import subprocess
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from repro.elastic.heartbeat import (DEFAULT_INTERVAL, hb_path,
                                     heartbeat_deadline, read_heartbeat)
from repro.elastic.policy import Action, RestartPolicy
from repro.elastic.worker import stop_path, worker_command, worker_env
from repro.robustness.faults import FaultPlan, FaultSpec

log = logging.getLogger(__name__)


@dataclass(frozen=True)
class FleetConfig:
    """One supervised fleet.  ``train_args`` is the worker argv tail (arch,
    steps, …) — the coordinator owns and injects the fleet handshake flags
    and the checkpoint directory, so they cannot diverge across ranks."""

    fleet_dir: str
    ckpt_dir: str
    world_size: int
    train_args: Tuple[str, ...] = ()
    min_world: int = 1
    target_world: int = 0          # 0 → world_size
    scale_up_at: int = 0           # chief step at which to restore target_world
    sync_interval: int = 8         # mirrors the workers' --sync-interval (deadline scaling)
    hb_interval: float = DEFAULT_INTERVAL
    poll_interval: float = 0.1
    startup_grace: float = 60.0    # first-heartbeat allowance (interpreter + jax import)
    drain_timeout: float = 600.0   # SIGTERM → exit allowance (covers an XLA compile)
    policy: RestartPolicy = field(default_factory=RestartPolicy)
    fault_plan: Optional[FaultPlan] = None

    @property
    def resolved_target(self) -> int:
        return self.target_world or self.world_size


@dataclass
class FleetResult:
    ok: bool
    exit_code: int
    reason: str
    world_history: List[int]
    events: List[dict]
    restarts: int
    wall_s: float

    def summary(self) -> dict:
        recoveries = [e for e in self.events
                      if e.get("recovery_s") is not None]
        return {
            "ok": self.ok, "exit_code": self.exit_code, "reason": self.reason,
            "world_history": self.world_history, "restarts": self.restarts,
            "wall_s": round(self.wall_s, 3),
            "n_events": len(self.events),
            "steps_lost_total": sum(e.get("steps_lost", 0)
                                    for e in self.events),
            "recovery_s_max": (max(e["recovery_s"] for e in recoveries)
                               if recoveries else 0.0),
            "events": self.events,
        }


@dataclass
class _Worker:
    rank: int
    proc: subprocess.Popen
    log_file: object
    launched_at: float             # time.time(), baselines the liveness check


class Coordinator:
    """Single-threaded supervisor: one poll loop owns all fleet state, and
    drains/resizes run synchronously inside it — no cross-thread races to
    reason about at the cost of (bounded, recorded) backoff sleeps."""

    def __init__(self, fc: FleetConfig, *,
                 command: Callable[..., List[str]] = worker_command,
                 env: Callable[..., Dict[str, str]] = worker_env):
        self.fc = fc
        self._command = command
        self._env = env
        self.world = fc.world_size
        self.events: List[dict] = []
        self.world_history: List[int] = [fc.world_size]
        self.restarts = 0
        self._workers: Dict[int, _Worker] = {}
        self._attempts: Dict[int, int] = {}
        self._pending_faults: List[FaultSpec] = (
            list(fc.fault_plan.fleet_faults()) if fc.fault_plan else [])
        self._grace_kill: Dict[int, float] = {}   # rank → SIGKILL deadline
        self._last_chief_step = -1
        self._t0 = 0.0

    # --------------------------------------------------------------- spawning
    def _train_argv(self) -> List[str]:
        args = list(self.fc.train_args)
        if self.fc.ckpt_dir:
            args += ["--ckpt", self.fc.ckpt_dir]
        return args

    def _spawn(self, rank: int) -> None:
        # stale artifacts from this rank's previous incarnation must not
        # satisfy the new one's liveness / stop checks
        for p in (hb_path(self.fc.fleet_dir, rank),
                  stop_path(self.fc.fleet_dir, rank)):
            if os.path.exists(p):
                os.remove(p)
        cmd = self._command(rank, self.world, self.fc.fleet_dir,
                            self._train_argv())
        logf = open(os.path.join(self.fc.fleet_dir,
                                 f"worker_{rank}.log"), "ab")
        proc = subprocess.Popen(cmd, stdout=logf, stderr=subprocess.STDOUT,
                                env=self._env(rank, self.world))
        self._workers[rank] = _Worker(rank=rank, proc=proc, log_file=logf,
                                      launched_at=time.time())
        log.info("fleet: launched rank %d/%d (pid %d)", rank, self.world,
                 proc.pid)

    def _launch_fleet(self) -> None:
        stop_all = stop_path(self.fc.fleet_dir)
        if os.path.exists(stop_all):
            os.remove(stop_all)
        for rank in range(self.world):
            if rank not in self._workers:
                self._spawn(rank)

    def _reap(self, rank: int) -> int:
        w = self._workers.pop(rank)
        rc = w.proc.wait()
        w.log_file.close()
        self._grace_kill.pop(rank, None)
        return rc

    # ------------------------------------------------------------- liveness
    def _chief_beat(self):
        hb = read_heartbeat(self.fc.fleet_dir, 0)
        if hb is not None and hb.step > self._last_chief_step:
            self._last_chief_step = hb.step
        return hb

    def _check_liveness(self, chief_ema: float) -> None:
        deadline = max(
            heartbeat_deadline(self.fc.hb_interval, chief_ema,
                               self.fc.sync_interval),
            # never tighter than the worst boundary stall we tolerate anyway
            self.fc.poll_interval * 4)
        now = time.time()
        for rank, w in list(self._workers.items()):
            if w.proc.poll() is not None:
                continue  # already exited; the exit handler owns it
            hb = read_heartbeat(self.fc.fleet_dir, rank)
            last = hb.time if hb is not None else w.launched_at
            allowance = deadline if hb is not None else max(
                deadline, self.fc.startup_grace)
            if now - max(last, w.launched_at) > allowance:
                log.warning("fleet: rank %d heartbeat silent %.1fs "
                            "(deadline %.1fs) — presumed wedged, SIGKILL",
                            rank, now - last, allowance)
                self._record(kind="hb_timeout", rank=rank,
                             silent_s=round(now - last, 3))
                w.proc.kill()  # surfaces as a crash exit on the next poll

    # ------------------------------------------------------- fault actuation
    def _actuate_faults(self, chief_step: int) -> None:
        while self._pending_faults and chief_step >= self._pending_faults[0].step:
            spec = self._pending_faults.pop(0)
            plan = self.fc.fault_plan
            victim = plan.victim_rank(spec, self.world)
            w = self._workers.get(victim)
            if w is None or w.proc.poll() is not None:
                self._record(kind=spec.kind, rank=victim, step=chief_step,
                             skipped="victim already down")
                continue
            if spec.kind == "worker_lost":
                log.warning("fault injection: worker_lost → SIGKILL rank %d "
                            "(chief step %d)", victim, chief_step)
                w.proc.kill()
            else:  # preempt: notice (SIGTERM) now, SIGKILL after the grace
                grace = plan.preempt_grace(spec)
                log.warning("fault injection: preempt rank %d, %.1fs grace "
                            "(chief step %d)", victim, grace, chief_step)
                w.proc.terminate()
                self._grace_kill[victim] = time.monotonic() + grace
            self._record(kind=spec.kind, rank=victim, step=chief_step,
                         arg=spec.arg)

    def _expire_grace(self) -> None:
        for rank, deadline in list(self._grace_kill.items()):
            if time.monotonic() < deadline:
                continue
            w = self._workers.get(rank)
            if w is not None and w.proc.poll() is None:
                log.warning("fleet: rank %d outlived its preemption grace — "
                            "SIGKILL", rank)
                w.proc.kill()
            self._grace_kill.pop(rank, None)

    # ------------------------------------------------------ drain and resize
    def _latest_ckpt_step(self) -> int:
        """Newest on-disk boundary step (manifest present).  Bookkeeping only:
        the relaunched chief does its own CRC-verified ``latest_valid()``
        walk — the coordinator never decides the resume point."""
        best = -1
        try:
            for d in os.listdir(self.fc.ckpt_dir):
                tail = d.split("_", 1)[-1]
                if d.startswith("step_") and tail.isdigit() and os.path.exists(
                        os.path.join(self.fc.ckpt_dir, d, "manifest.json")):
                    best = max(best, int(tail))
        except OSError:
            pass
        return best

    def _drain_survivors(self) -> None:
        """SIGTERM every live worker and wait: the chief finishes its in-flight
        block, writes a synchronous boundary checkpoint, and exits 75; the
        followers exit 75 immediately.  Wedged workers are SIGKILLed after
        ``drain_timeout`` (the chief then resumes from the last periodic
        boundary checkpoint instead — later, but still bit-exact)."""
        for w in self._workers.values():
            if w.proc.poll() is None:
                w.proc.terminate()
        deadline = time.monotonic() + self.fc.drain_timeout
        for rank in list(self._workers):
            w = self._workers[rank]
            remaining = deadline - time.monotonic()
            try:
                w.proc.wait(timeout=max(remaining, 0.1))
            except subprocess.TimeoutExpired:
                log.warning("fleet: rank %d did not drain in %.0fs — SIGKILL",
                            rank, self.fc.drain_timeout)
                w.proc.kill()
            self._reap(rank)

    def _resize(self, new_world: int, *, reason: str) -> None:
        t0 = time.monotonic()
        step_before = self._last_chief_step
        self._drain_survivors()
        ckpt_step = self._latest_ckpt_step()
        self.world = new_world
        self.world_history.append(new_world)
        self._attempts = {}            # a resize is a fresh scheduling epoch
        self._grace_kill = {}
        self._launch_fleet()
        recovery = self._await_chief_beat()
        self._record(kind="resize", reason=reason,
                     world_to=new_world, ckpt_step=ckpt_step,
                     steps_lost=max(0, step_before - max(ckpt_step, 0)),
                     recovery_s=round(time.monotonic() - t0, 3),
                     chief_rebeat_s=recovery)

    def _await_chief_beat(self) -> Optional[float]:
        """Block until the relaunched chief's first beat (bounded by the
        startup grace) — the honest end of a recovery interval."""
        t0 = time.monotonic()
        w = self._workers.get(0)
        while time.monotonic() - t0 < self.fc.startup_grace:
            hb = read_heartbeat(self.fc.fleet_dir, 0)
            if hb is not None and w is not None and hb.pid == w.proc.pid:
                return round(time.monotonic() - t0, 3)
            time.sleep(self.fc.poll_interval)
        return None

    def _stop_fleet(self) -> None:
        """Terminal shutdown: stop-file first (followers exit 0), then
        SIGTERM, then SIGKILL past the drain timeout."""
        with open(stop_path(self.fc.fleet_dir), "w") as f:
            f.write("stop")
        time.sleep(min(0.3, self.fc.drain_timeout))
        self._drain_survivors()

    # ------------------------------------------------------------ exits
    def _handle_exit(self, rank: int, rc: int) -> Optional[FleetResult]:
        attempt = self._attempts.get(rank, 0)
        decision = self.fc.policy.decide(rc, rank, attempt)
        step = self._last_chief_step
        ckpt_step = self._latest_ckpt_step()
        lost = max(0, step - max(ckpt_step, 0)) if rank == 0 else 0
        self._record(kind="worker_exit", rank=rank, rc=rc, step=step,
                     action=decision.action.value, reason=decision.reason,
                     delay_s=round(decision.delay_s, 3) or None,
                     steps_lost=lost or None)
        if decision.action is Action.DONE:
            if rank == 0:
                self._stop_fleet()  # followers exit 0 via the stop file
                return self._finish(ok=True, exit_code=0,
                                    reason="chief finished")
            # A follower finishing unprompted mid-run is not part of the
            # protocol; keep the slot filled and let liveness sort it out.
            self._spawn(rank)
            return None
        if decision.action is Action.RESUME:
            t0 = time.monotonic()
            self._attempts[rank] = 0   # a clean drain resets the slot's budget
            self._spawn(rank)
            self.restarts += 1
            if rank == 0:
                self._record(kind="resume", rank=rank, ckpt_step=ckpt_step,
                             recovery_s=self._await_chief_beat() or
                             round(time.monotonic() - t0, 3))
            return None
        if decision.action is Action.RESTART:
            self._attempts[rank] = attempt + 1
            time.sleep(decision.delay_s)
            t0 = time.monotonic()
            self._spawn(rank)
            self.restarts += 1
            if rank == 0:
                self._record(kind="restart", rank=rank, ckpt_step=ckpt_step,
                             steps_lost=lost,
                             recovery_s=self._await_chief_beat() or
                             round(time.monotonic() - t0, 3))
            return None
        if decision.action is Action.ESCALATE:
            self._stop_fleet()
            return self._finish(ok=False, exit_code=rc, reason=decision.reason)
        # GIVE_UP: degrade if the fleet floor allows, halt otherwise
        if self.world - 1 >= self.fc.min_world:
            self._resize(self.world - 1,
                         reason=f"rank {rank} lost past restart budget")
            return None
        self._stop_fleet()
        return self._finish(
            ok=False, exit_code=rc,
            reason=f"{decision.reason}; already at min_world="
                   f"{self.fc.min_world}")

    def _finish(self, *, ok: bool, exit_code: int, reason: str) -> FleetResult:
        result = FleetResult(ok=ok, exit_code=exit_code, reason=reason,
                             world_history=self.world_history,
                             events=self.events, restarts=self.restarts,
                             wall_s=time.monotonic() - self._t0)
        with open(os.path.join(self.fc.fleet_dir, "fleet_summary.json"),
                  "w") as f:
            json.dump(result.summary(), f, indent=1)
        return result

    def _record(self, **event) -> None:
        event = {k: v for k, v in event.items() if v is not None}
        event["t"] = round(time.monotonic() - self._t0, 3)
        event["world"] = self.world
        self.events.append(event)
        try:
            with open(os.path.join(self.fc.fleet_dir, "events.jsonl"),
                      "a") as f:
                f.write(json.dumps(event) + "\n")
        except OSError:
            pass

    # ------------------------------------------------------------------ run
    def run(self, timeout: Optional[float] = None) -> FleetResult:
        self._t0 = time.monotonic()
        os.makedirs(self.fc.fleet_dir, exist_ok=True)
        self._launch_fleet()
        try:
            while True:
                if timeout is not None and \
                        time.monotonic() - self._t0 > timeout:
                    self._stop_fleet()
                    return self._finish(ok=False, exit_code=124,
                                        reason="coordinator timeout")
                time.sleep(self.fc.poll_interval)
                hb = self._chief_beat()
                chief_step = self._last_chief_step
                self._actuate_faults(chief_step)
                self._expire_grace()
                if (self.fc.scale_up_at and chief_step >= self.fc.scale_up_at
                        and self.world < self.fc.resolved_target):
                    self._resize(self.fc.resolved_target, reason="scale_up")
                    continue
                for rank in sorted(self._workers):
                    w = self._workers.get(rank)
                    if w is not None and w.proc.poll() is not None:
                        result = self._handle_exit(rank, self._reap(rank))
                        if result is not None:
                            return result
                self._check_liveness(hb.ema_dt if hb else 0.0)
        finally:
            # belt-and-braces: never leave orphan workers behind an exception
            for w in self._workers.values():
                if w.proc.poll() is None:
                    w.proc.kill()
            for rank in list(self._workers):
                self._reap(rank)


# ------------------------------------------------------------------- CLI
def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Elastic fleet supervisor: spawn/watch/restart/resize a "
                    "multi-process training fleet (DESIGN.md §4b).  Worker "
                    "args go after `--`, e.g.: python -m "
                    "repro.elastic.coordinator --world-size 4 --ckpt /tmp/ck "
                    "--fleet-dir /tmp/fleet -- --arch qwen3-0.6b --reduced "
                    "--steps 64 --sync-interval 4 --ckpt-every 4")
    ap.add_argument("--world-size", type=int, required=True)
    ap.add_argument("--min-world", type=int, default=1)
    ap.add_argument("--target-world", type=int, default=0)
    ap.add_argument("--scale-up-at", type=int, default=0,
                    help="chief step at which to restore target world size")
    ap.add_argument("--fleet-dir", required=True)
    ap.add_argument("--ckpt", required=True,
                    help="checkpoint dir (owned by the coordinator and "
                         "forwarded to every worker)")
    ap.add_argument("--sync-interval", type=int, default=8,
                    help="forwarded to workers; also scales the heartbeat "
                         "deadline (EMA is per-step, deadlines are per-block)")
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--backoff-base", type=float, default=0.25)
    ap.add_argument("--drain-timeout", type=float, default=600.0)
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="KIND@STEP[:ARG]",
                    help="fleet-level faults: preempt@step[:grace_s], "
                         "worker_lost@step[:rank]")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=0.0,
                    help="overall supervisor timeout (0 = none)")
    ap.add_argument("train_args", nargs=argparse.REMAINDER,
                    help="worker args after `--` (passed to repro.launch.train)")
    args = ap.parse_args(argv)

    train_args = list(args.train_args)
    if train_args and train_args[0] == "--":
        train_args = train_args[1:]
    train_args += ["--sync-interval", str(args.sync_interval)]
    fc = FleetConfig(
        fleet_dir=args.fleet_dir, ckpt_dir=args.ckpt,
        world_size=args.world_size, min_world=args.min_world,
        target_world=args.target_world, scale_up_at=args.scale_up_at,
        sync_interval=args.sync_interval,
        drain_timeout=args.drain_timeout,
        train_args=tuple(train_args),
        policy=RestartPolicy(max_restarts=args.max_restarts,
                             backoff_base=args.backoff_base,
                             seed=args.fault_seed),
        fault_plan=(FaultPlan.parse(args.inject_fault, seed=args.fault_seed)
                    if args.inject_fault else None))
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s coordinator %(message)s")
    result = Coordinator(fc).run(timeout=args.timeout or None)
    print(json.dumps({k: v for k, v in result.summary().items()
                      if k != "events"}, indent=1))
    return 0 if result.ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
