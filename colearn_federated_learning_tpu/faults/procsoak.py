"""Multi-process chaos soak: real subprocesses, real ports, real SIGKILL.

The in-process soak (faults/soak.py) exercises the robustness machinery
through a transport interposer — everything a Python exception can
express.  This harness exercises what it cannot: fd leaks, half-written
frames, torn files and lost process state.  It spawns the broker, N
``colearn worker`` processes and a ``colearn coordinate`` process on real
sockets, then delivers ``SIGKILL`` on a deterministic schedule keyed by
round — including to the coordinator mid-round, which must come back with
``--resume`` and finish the original round budget from its checkpoint +
round WAL, and to the broker, which is respawned on its original port
and must be healed INTO by the survivors (worker re-enrollment
watchdogs, coordinator ``_rebuild_broker``) without losing a round.

The schedule is event-driven, not timer-driven: a :class:`KillSpec`
fires the moment the coordinator's stderr emits the round record for
``after_round``, so the signal lands while the NEXT round is in flight.
That keeps the soak deterministic in ROUND time even though wall-clock
varies run to run.

``scripts/chaos_soak_mp.py`` wraps this in a baseline-vs-faulted
convergence gate; ``colearn chaos --mp`` is the one-run flavor.
"""

from __future__ import annotations

import dataclasses
import json
import os
import select
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Optional

_CLI = "colearn_federated_learning_tpu.cli"


@dataclasses.dataclass(frozen=True)
class KillSpec:
    """One scheduled SIGKILL.

    ``target`` is ``"coordinator"``, ``"async-coordinator"``,
    ``"broker"``, ``"worker:<client_id>"`` or ``"aggregator:<n>"``.
    The signal is sent as soon as the round record for ``after_round``
    appears, i.e. it lands mid-round ``after_round + 1`` (for the
    buffered-async plane ``after_round`` counts AGGREGATIONS — the kill
    lands mid-aggregation, while dispatcher pumps are in flight).
    ``restart`` respawns the victim: a worker re-announces on a fresh
    port (and is re-admitted by the elastic coordinator after
    eviction), the coordinator comes back with ``--resume``, and the
    broker rebinds its ORIGINAL port — the control-plane SPOF heals
    through the worker re-enrollment watchdog and the coordinator's
    ``_rebuild_broker`` without any address change.  An aggregator is
    the one role that may STAY dead (``restart=False``): the root must
    re-home its slice onto a sibling or quorum-drop it — that failover
    IS the thing the agg soak gates on."""

    target: str
    after_round: int
    restart: bool = True

    def __post_init__(self):
        singletons = ("coordinator", "async-coordinator", "broker")
        if self.target not in singletons and not (
                self.target.split(":", 1)[0] in ("worker", "aggregator")
                and ":" in self.target
                and self.target.split(":", 1)[1].isdigit()):
            raise ValueError(
                f"target must be 'coordinator', 'async-coordinator', "
                f"'broker', 'worker:<id>' or 'aggregator:<n>', "
                f"got {self.target!r}")
        if self.after_round < 0:
            raise ValueError(
                f"after_round must be >= 0, got {self.after_round}")
        if self.target in singletons and not self.restart:
            raise ValueError(
                f"killing the {self.target} without restart ends the "
                "federation; use restart=True")


def canned_kill_schedule(rounds: int, n_workers: int) -> list[KillSpec]:
    """The acceptance schedule, scaled to the run length:

    - a worker dies mid-round 2 and restarts (exercises eviction +
      elastic re-admission on a fresh port) — only when the run is long
      enough for it to be evicted AND re-converge;
    - the coordinator dies mid-round ``rounds // 2 + 1``, after the
      round-``rounds//2`` checkpoint committed, and must resume;
    - the broker dies one round after the coordinator resumed and
      rebinds its original port (control-plane SPOF: worker watchdogs
      re-enroll, the coordinator rebuilds its client) — only when the
      run leaves at least one full round after the rebind to prove the
      federation still commits.
    """
    kills = []
    if rounds >= 5 and n_workers >= 3:
        kills.append(KillSpec("worker:1", after_round=1))
    kills.append(KillSpec("coordinator",
                          after_round=max(0, rounds // 2 - 1)))
    if rounds >= 4:
        kills.append(KillSpec("broker", after_round=rounds // 2))
    return kills


def _config_flags(rounds: int, n_workers: int, seed: int,
                  checkpoint_dir: Optional[str] = None) -> list[str]:
    """CLI overrides reproducing faults/soak.default_soak_config — same
    tiny CPU federation, robustness features ON."""
    flags = [
        "--config", "mnist_mlp_fedavg", "--backend", "cpu",
        "--dataset", "mnist_tiny", "--partition", "iid",
        "--num-clients", str(n_workers), "--rounds", str(rounds),
        "--cohort-size", "0", "--local-steps", "4", "--batch-size", "16",
        "--lr", "0.05", "--momentum", "0.0", "--strategy", "fedavg",
        "--min-cohort-fraction", "0.5", "--evict-after", "2",
        "--comm-retries", "2", "--seed", str(seed),
    ]
    if checkpoint_dir:
        flags += ["--checkpoint-dir", checkpoint_dir,
                  "--checkpoint-every", "1"]
    return flags


def _parse_json(line: str) -> Optional[dict]:
    try:
        doc = json.loads(line)
    except json.JSONDecodeError:
        return None            # ordinary log chatter on the same stream
    return doc if isinstance(doc, dict) else None


class _Fleet:
    """Process bookkeeping for one soak run (spawn/kill/cleanup)."""

    def __init__(self, workdir: str, env: dict):
        self.workdir = workdir
        self.env = env
        self.broker: Optional[subprocess.Popen] = None
        self.workers: dict[int, subprocess.Popen] = {}
        self.aggregators: dict[int, subprocess.Popen] = {}
        self.coord: Optional[subprocess.Popen] = None
        self._logs: list = []

    def _log_file(self, name: str):
        f = open(os.path.join(self.workdir, name), "ab")
        self._logs.append(f)
        return f

    def spawn(self, args: list[str], **kw) -> subprocess.Popen:
        return subprocess.Popen([sys.executable, "-m", _CLI, *args],
                                env=self.env, **kw)

    def start_broker(self, timeout: float,
                     extra: list[str] = ()) -> tuple[str, int]:
        self._broker_extra = list(extra)
        self.broker = self.spawn(
            ["broker", *self._broker_extra], stdout=subprocess.PIPE,
            stderr=self._log_file("broker.log"), text=True)
        ready, _, _ = select.select([self.broker.stdout], [], [], timeout)
        if not ready:
            raise RuntimeError("broker never announced its port")
        doc = _parse_json(self.broker.stdout.readline())
        if not doc:
            raise RuntimeError("broker printed no address line")
        self._broker_addr = (doc["host"], int(doc["port"]))
        return self._broker_addr

    def restart_broker(self, timeout: float = 15.0,
                       attempts: int = 20) -> None:
        """Respawn the broker bound to its ORIGINAL host:port.

        Workers and the coordinator hold that address — the heal paths
        (worker re-enrollment watchdog, coordinator ``_rebuild_broker``)
        reconnect, they do not rediscover.  The listener socket dies
        with the SIGKILLed process, but the kernel may briefly hold the
        port through lingering accepted connections, so the rebind
        retries with a short sleep instead of failing the soak on a
        race the real deployment would also just retry through."""
        host, port = self._broker_addr
        for _ in range(attempts):
            self.broker = self.spawn(
                ["broker", "--host", host, "--port", str(port),
                 *self._broker_extra],
                stdout=subprocess.PIPE,
                stderr=self._log_file("broker.log"), text=True)
            ready, _, _ = select.select([self.broker.stdout], [], [],
                                        timeout)
            if ready:
                doc = _parse_json(self.broker.stdout.readline())
                if doc and int(doc["port"]) == port:
                    return
            if self.broker.poll() is None:
                self.broker.kill()
            self.broker.wait()
            time.sleep(0.25)
        raise RuntimeError(f"broker failed to rebind {host}:{port} "
                           f"after {attempts} attempts")

    def start_worker(self, client_id: int, cfg: list[str], host: str,
                     port: int) -> None:
        log = self._log_file(f"worker{client_id}.log")
        self.workers[client_id] = self.spawn(
            ["worker", *cfg, "--client-id", str(client_id),
             "--broker-host", host, "--broker-port", str(port)],
            stdout=log, stderr=log)

    def start_aggregator(self, agg_id: int, cfg: list[str], host: str,
                         port: int) -> None:
        log = self._log_file(f"aggregator{agg_id}.log")
        self.aggregators[agg_id] = self.spawn(
            ["aggregator", *cfg, "--agg-id", str(agg_id),
             "--broker-host", host, "--broker-port", str(port)],
            stdout=log, stderr=log)

    def start_coordinator(self, cfg: list[str], host: str, port: int,
                          n_workers: int, round_timeout: float,
                          enroll_timeout: float,
                          resume: bool) -> subprocess.Popen:
        args = ["coordinate", *cfg, "--broker-host", host,
                "--broker-port", str(port),
                "--min-devices", str(n_workers),
                "--round-timeout", str(round_timeout),
                "--enroll-timeout", str(enroll_timeout),
                "--no-evaluator", "--per-client-eval", "--elastic"]
        if resume:
            args.append("--resume")
        self.coord = self.spawn(
            args, stdout=self._log_file("coordinator.out"),
            stderr=subprocess.PIPE, text=True)
        return self.coord

    def start_async_coordinator(self, cfg: list[str], host: str, port: int,
                                n_workers: int, round_timeout: float,
                                enroll_timeout: float, buffer_size: int,
                                resume: bool) -> subprocess.Popen:
        """Buffered-async flavor of :meth:`start_coordinator`:
        ``--async-buffer`` switches the CLI onto
        comm/async_coordinator.py, which has no per-client eval plane —
        the gate compares train-loss trajectories instead."""
        args = ["coordinate", *cfg, "--broker-host", host,
                "--broker-port", str(port),
                "--min-devices", str(n_workers),
                "--round-timeout", str(round_timeout),
                "--enroll-timeout", str(enroll_timeout),
                "--async-buffer", str(buffer_size),
                "--no-evaluator", "--elastic"]
        if resume:
            args.append("--resume")
        self.coord = self.spawn(
            args, stdout=self._log_file("coordinator.out"),
            stderr=subprocess.PIPE, text=True)
        return self.coord

    def _all_procs(self) -> list:
        return ([self.coord, self.broker] + list(self.workers.values())
                + list(self.aggregators.values()))

    def kill_all(self) -> None:
        for p in self._all_procs():
            if p is not None and p.poll() is None:
                p.kill()

    def close(self) -> None:
        self.kill_all()
        for p in self._all_procs():
            if p is not None:
                p.wait()
        for f in self._logs:
            f.close()


def run_proc_soak(
    rounds: int = 6,
    n_workers: int = 3,
    kills: Optional[list[KillSpec]] = None,
    workdir: Optional[str] = None,
    round_timeout: float = 120.0,
    enroll_timeout: float = 90.0,
    timeout_s: float = 600.0,
    seed: int = 0,
    n_aggregators: int = 0,
    health: bool = False,
    log_fn: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Run one multi-process soak and return its summary.

    The summary mirrors faults/soak.run_soak where the concepts overlap
    (``records`` — deduplicated by round, LAST record wins so a resumed
    re-run of an uncommitted round replaces the lost one — plus
    ``skipped_rounds``, ``evicted``, ``per_client_acc``) and adds the
    process-level ledger: ``kills`` delivered, ``rounds_resumed`` (count
    of successful ``--resume`` recoveries, reported by the coordinator's
    resume event line), ``coordinator_incarnations``, the final
    ``exit_code``, and the flight ledger — ``flight_dumps`` (parseable
    black boxes found) and ``flight_missing`` (SIGKILLed pids that left
    no parseable dump; must be empty)."""
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    kills = list(kills or [])
    for k in kills:
        if k.target.startswith("worker:"):
            wid = int(k.target.split(":", 1)[1])
            if not 0 <= wid < n_workers:
                raise ValueError(f"{k.target} out of range "
                                 f"[0, {n_workers})")
        elif k.target.startswith("aggregator:"):
            aid = int(k.target.split(":", 1)[1])
            if not 0 <= aid < n_aggregators:
                raise ValueError(f"{k.target} out of range "
                                 f"[0, {n_aggregators})")
    workdir = workdir or tempfile.mkdtemp(prefix="colearn_mpsoak_")
    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = os.path.join(workdir, "ckpt")
    flight_dir = os.path.join(workdir, "flight")

    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"      # round records must stream, not batch
    env["JAX_PLATFORMS"] = "cpu"       # a CPU tool: many processes, no chip

    fleet = _Fleet(workdir, env)
    # Hard wall-clock backstop: a hung federation (the exact bug class
    # this harness hunts) must fail the run, not the CI job's timeout.
    watchdog = threading.Timer(timeout_s, fleet.kill_all)
    watchdog.daemon = True

    records: dict[int, dict] = {}
    events: list[dict] = []
    per_client: dict = {}
    resumed = 0
    incarnations = 1
    delivered: list[dict] = []
    pending = sorted(kills, key=lambda k: (k.after_round, k.target))
    rc: Optional[int] = None

    try:
        watchdog.start()
        # Every process flies with the black box on a fast heartbeat: a
        # SIGKILL is uncatchable, so the per-kill dump the summary
        # asserts below IS the victim's last heartbeat rewrite.  The
        # broker carries it too — a broker KillSpec's pid must show up
        # in the flight ledger like any other victim's.
        flight_flags = ["--flight-dir", flight_dir,
                        "--flight-heartbeat", "0.5"]
        # Health flags ride on the federation roles only — the broker's
        # parser has no override flags, and the ledger is written by the
        # coordinator/aggregator planes anyway.
        health_flags = (["--health-dir", os.path.join(workdir, "health")]
                        if health else [])
        host, port = fleet.start_broker(timeout=30.0, extra=flight_flags)
        worker_cfg = (_config_flags(rounds, n_workers, seed)
                      + flight_flags + health_flags)
        for i in range(n_workers):
            fleet.start_worker(i, worker_cfg, host, port)
        # Aggregator tier (tree ingest): spawned between broker and
        # coordinator so the retained announcements are on the broker
        # before the root's enroll_aggregators() subscribes.
        agg_cfg = worker_cfg
        for a in range(n_aggregators):
            fleet.start_aggregator(a, agg_cfg, host, port)
        coord_cfg = (_config_flags(rounds, n_workers, seed,
                                   checkpoint_dir=ckpt_dir)
                     + flight_flags + health_flags)
        if n_aggregators:
            coord_cfg += ["--num-aggregators", str(n_aggregators)]

        def launch(resume: bool) -> subprocess.Popen:
            return fleet.start_coordinator(
                coord_cfg, host, port, n_workers, round_timeout,
                enroll_timeout, resume=resume)

        coord = launch(resume=False)
        restart_pending = False
        # Mirror the coordinator's stderr to a workdir log: the harness
        # parses JSON records off the stream, but a crash traceback is
        # NOT JSON and would otherwise vanish with the pipe.
        err_log = fleet._log_file("coordinator.err")
        while True:
            line = coord.stderr.readline()
            if line:
                err_log.write(line.encode())
                err_log.flush()
            if not line:
                coord.wait()
                if restart_pending:
                    restart_pending = False
                    incarnations += 1
                    coord = launch(resume=True)
                    continue
                rc = coord.returncode
                break
            doc = _parse_json(line.strip())
            if doc is None:
                continue
            if "event" in doc:
                events.append(doc)
                if doc["event"] == "resumed":
                    resumed += 1
                continue
            if "num_clients_evaluated" in doc:
                per_client = doc
                continue
            if "round" not in doc:
                continue
            r = int(doc["round"])
            records[r] = doc           # last record per round wins
            if log_fn is not None:
                log_fn(doc)
            while pending and pending[0].after_round <= r:
                spec = pending.pop(0)
                kill_rec = {**dataclasses.asdict(spec),
                            "fired_after_round": r}
                if spec.target == "coordinator":
                    kill_rec["pid"] = coord.pid
                    coord.send_signal(signal.SIGKILL)
                    restart_pending = True
                elif spec.target == "broker":
                    victim = fleet.broker
                    if victim is not None and victim.poll() is None:
                        kill_rec["pid"] = victim.pid
                        victim.send_signal(signal.SIGKILL)
                        victim.wait()
                    fleet.restart_broker()
                elif spec.target.startswith("aggregator:"):
                    aid = int(spec.target.split(":", 1)[1])
                    victim = fleet.aggregators.get(aid)
                    if victim is not None and victim.poll() is None:
                        kill_rec["pid"] = victim.pid
                        victim.send_signal(signal.SIGKILL)
                        victim.wait()
                    if spec.restart:
                        fleet.start_aggregator(aid, agg_cfg, host, port)
                else:
                    wid = int(spec.target.split(":", 1)[1])
                    victim = fleet.workers.get(wid)
                    if victim is not None and victim.poll() is None:
                        kill_rec["pid"] = victim.pid
                        victim.send_signal(signal.SIGKILL)
                        victim.wait()
                    if spec.restart:
                        fleet.start_worker(wid, worker_cfg, host, port)
                delivered.append(kill_rec)
    finally:
        watchdog.cancel()
        fleet.close()

    if rc is None:
        raise RuntimeError(
            f"coordinator never exited cleanly within {timeout_s}s "
            f"(records for rounds {sorted(records)})")

    # Flight-dump ledger: every SIGKILLed pid must have left a parseable
    # black box (the acceptance criterion the flight recorder exists
    # for).  A dump that exists but does not parse counts as missing —
    # the atomic-write contract says a dump either parses or is absent.
    from colearn_federated_learning_tpu.telemetry import flight as _flight

    dumps = _flight.load_flight_dumps(flight_dir)
    dumped_pids = {d.get("pid") for d in dumps if "error" not in d}
    flight_missing = sorted({k["pid"] for k in delivered if "pid" in k}
                            - dumped_pids)

    recs = [records[r] for r in sorted(records)]
    return {
        "rounds_run": len(recs),
        "records": recs,
        "completed_rounds": [r["round"] for r in recs
                             if r["completed"] > 0
                             and not r.get("skipped_quorum")],
        "skipped_rounds": [r["round"] for r in recs
                           if r.get("skipped_quorum")],
        "evicted": sorted({d for r in recs for d in r.get("evicted", [])}),
        "weighted_acc": per_client.get("weighted_acc"),
        "weighted_loss": per_client.get("weighted_loss"),
        "per_client_acc": per_client.get("per_client", {}),
        "rounds_resumed": resumed,
        "coordinator_incarnations": incarnations,
        "agg_failovers": sum(int(r.get("agg_failovers", 0)) for r in recs),
        "kills": delivered,
        "flight_dumps": len(dumped_pids),
        "flight_missing": flight_missing,
        "events": events,
        "exit_code": rc,
        "workdir": workdir,
    }


def _final_checkpoint_state(ckpt_dir: str):
    """Load the server state from the LATEST checkpoint under
    ``ckpt_dir`` without a target template (the harness has no model —
    the saved metadata carries the tree structure and dtypes)."""
    import orbax.checkpoint as ocp

    mgr = ocp.CheckpointManager(os.path.abspath(ckpt_dir))
    try:
        step = mgr.latest_step()
        if step is None:
            return None, None
        restored = mgr.restore(
            step, args=ocp.args.Composite(state=ocp.args.StandardRestore()))
        return restored["state"], step
    finally:
        mgr.close()


def _max_param_diff(state_a, state_b) -> float:
    """Max abs elementwise difference across two server-state pytrees
    (leaf-path aligned; a structure mismatch is itself a failure and
    surfaces as ``inf``)."""
    import jax
    import numpy as np

    la, ta = jax.tree_util.tree_flatten_with_path(state_a)
    lb, tb = jax.tree_util.tree_flatten_with_path(state_b)
    if ta != tb or [p for p, _ in la] != [p for p, _ in lb]:
        return float("inf")
    worst = 0.0
    for (_, a), (_, b) in zip(la, lb):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape:
            return float("inf")
        if a.size:
            worst = max(worst, float(np.max(np.abs(
                a.astype(np.float64) - b.astype(np.float64)))))
    return worst


def run_agg_soak(
    rounds: int = 4,
    n_workers: int = 3,
    workdir: Optional[str] = None,
    round_timeout: float = 120.0,
    enroll_timeout: float = 90.0,
    timeout_s: float = 600.0,
    kill: bool = True,
    seed: int = 0,
    tol: float = 2e-4,
    log_fn: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Aggregator-tree chaos gate: tree soak under a real aggregator
    SIGKILL, lockstep against a flat (no-tree) oracle.

    Two full subprocess federations with identical config and seed:

    - **tree** — 2 aggregator processes own the device slices; with
      ``kill=True`` aggregator 0 is SIGKILLed mid-round (and stays
      dead), so the root must re-home its slice onto aggregator 1 or
      quorum-drop it (``agg_failovers >= 1`` in the round records);
    - **oracle** — the same federation folding flat at the root, no
      kills.

    The gate then compares the FINAL checkpointed server state of both
    runs: re-homing must lose no contribution, so the tree run's params
    stay within ``tol`` of the oracle's (the slack covers fold-order
    float non-associativity between arrival-order flat folds and
    slice-blocked tree folds, same bound as the secure-soak gate).  The
    killed aggregator must also have left a parseable flight dump whose
    postmortem attributes the death to the aggregator role, and the tree
    run's ``--health-dir`` ledgers must survive the kill: parseable and
    non-empty (``health_ledger_ok``/``health_devices`` in the summary,
    the same files `colearn health <workdir>/tree/health` renders)."""
    workdir = workdir or tempfile.mkdtemp(prefix="colearn_aggsoak_")
    os.makedirs(workdir, exist_ok=True)
    kills = ([KillSpec("aggregator:0",
                       after_round=max(0, rounds // 2 - 1),
                       restart=False)]
             if kill else [])

    tree = run_proc_soak(
        rounds=rounds, n_workers=n_workers, kills=kills,
        workdir=os.path.join(workdir, "tree"),
        round_timeout=round_timeout, enroll_timeout=enroll_timeout,
        timeout_s=timeout_s, seed=seed, n_aggregators=2, health=True,
        log_fn=log_fn)
    # The oracle flies with the health plane too: the ledger's per-round
    # fsync shifts arrival timing, and the flat fold is arrival-order —
    # an asymmetric config costs an ulp of fold-order noise in the
    # param comparison for no reason.
    oracle = run_proc_soak(
        rounds=rounds, n_workers=n_workers, kills=[],
        workdir=os.path.join(workdir, "flat"),
        round_timeout=round_timeout, enroll_timeout=enroll_timeout,
        timeout_s=timeout_s, seed=seed, n_aggregators=0, health=True,
        log_fn=log_fn)

    state_t, step_t = _final_checkpoint_state(
        os.path.join(workdir, "tree", "ckpt"))
    state_o, step_o = _final_checkpoint_state(
        os.path.join(workdir, "flat", "ckpt"))
    if state_t is None or state_o is None or step_t != step_o:
        max_diff = float("inf")
    else:
        max_diff = _max_param_diff(state_t, state_o)
    oracle_ok = max_diff <= tol

    # Postmortem attribution: the killed aggregator's black box must be
    # in the tree run's flight ledger AND the merged report must name
    # the victim as an aggregator — the same artifact `colearn
    # postmortem --flight-dir <workdir>/tree/flight` shows an operator.
    from colearn_federated_learning_tpu.telemetry import flight as _flight

    killed_pids = {k["pid"] for k in tree["kills"] if "pid" in k}
    if killed_pids:
        dumps = _flight.load_flight_dumps(
            os.path.join(workdir, "tree", "flight"))
        report = _flight.postmortem_report(dumps)
        attributed = any(
            p.get("pid") in killed_pids
            and str(p.get("role", "")).startswith("aggregator")
            for p in report.get("processes", []))
    else:
        attributed = not kill

    # Health-ledger durability: every tree role flew with --health-dir,
    # and the fsync-per-flush WAL discipline means the SIGKILLed
    # aggregator's per-device records must still PARSE (a torn final
    # line is tolerated; mid-file corruption raises) and must not be
    # empty — straggler attribution that dies with its process is no
    # attribution at all.
    from colearn_federated_learning_tpu.telemetry import health as _health

    try:
        devices = _health.load_health(os.path.join(workdir, "tree",
                                                   "health"))
    except ValueError:
        devices = {}
    health_ok = bool(devices)

    return {
        "exit_code": tree["exit_code"],
        "oracle_exit_code": oracle["exit_code"],
        "rounds_run": tree["rounds_run"],
        "oracle_rounds_run": oracle["rounds_run"],
        "oracle_ok": oracle_ok,
        "max_param_diff": max_diff,
        "checkpoint_step": step_t,
        "agg_failovers": tree["agg_failovers"],
        "postmortem_attributed": attributed,
        "health_ledger_ok": health_ok,
        "health_devices": len(devices),
        "flight_missing": tree["flight_missing"],
        "kills": tree["kills"],
        "records": tree["records"],
        "workdir": workdir,
    }


def _async_config_flags(aggregations: int, n_workers: int, seed: int,
                        checkpoint_dir: Optional[str] = None) -> list[str]:
    """The async-soak federation: the sync soak's tiny CPU config plus a
    fixed-clip DP mechanism, so every aggregation record carries the
    realized ``dp_z_eff``/``dp_epsilon`` the replay gate re-derives.
    ``--evict-after`` is loosened vs the sync soak's 2: injected
    client-side flaps land as consecutive pump failures, and the gate
    wants them ATTRIBUTED (health ledger retries), not escalated into
    evictions of perfectly healthy workers.  The noise multiplier is
    deliberately tiny: the replay gate needs every aggregation CHARGED
    (any mechanism will do), while the loss-parity gate needs both runs
    to actually converge — production-grade noise on a 3-client toy
    federation swamps the clipped deltas and both trajectories
    diverge."""
    flags = _config_flags(aggregations, n_workers, seed,
                          checkpoint_dir=checkpoint_dir)
    flags += ["--evict-after", "4",
              "--dp-clip", "1.0",
              "--dp-noise-multiplier", str(_ASYNC_DP_NOISE),
              "--dp-delta", str(_ASYNC_DP_DELTA)]
    return flags


_ASYNC_DP_DELTA = 1e-5
_ASYNC_DP_NOISE = 0.02


def _async_fault_plan() -> dict:
    """Client-site transport faults for the FAULTED async run: the plan
    is installed in the coordinator process (``--fault-plan``), so these
    fire inside the dispatcher pumps' ``TensorClient.request`` calls —
    flaps surface as pump failures the health ledger must attribute as
    retries, delays stretch the per-device latency EWMA.  Count-bounded
    so the run still converges."""
    return {"seed": 0, "faults": [
        {"kind": "flap_reconnect", "device_id": "*", "op": "train",
         "count": 2, "site": "client"},
        {"kind": "delay", "device_id": "*", "op": "train",
         "ms": 150, "count": 3, "site": "client"},
    ]}


def _run_async_fleet(
    aggregations: int,
    n_workers: int,
    buffer_size: int,
    kills: list[KillSpec],
    workdir: str,
    round_timeout: float,
    enroll_timeout: float,
    timeout_s: float,
    seed: int,
    n_aggregators: int = 0,
    fault_plan: Optional[dict] = None,
    log_fn: Optional[Callable[[dict], None]] = None,
    lock_witness: bool = False,
) -> dict:
    """One buffered-async subprocess federation (broker + N workers +
    async coordinator), with the proc-soak kill loop re-keyed on
    AGGREGATION records: the async plane logs ``{"aggregation": i,
    "model_version": v, ...}`` lines instead of round records, and a
    ``KillSpec("async-coordinator", after_round=k)`` fires the moment
    aggregation ``k``'s record appears — mid-aggregation ``k + 1``,
    while dispatcher pumps are in flight.  Records are deduplicated by
    aggregation index (LAST wins: a resumed incarnation's re-run of an
    uncommitted aggregation replaces the lost one), and model-version
    monotonicity is checked per incarnation as the stream arrives."""
    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = os.path.join(workdir, "ckpt")
    flight_dir = os.path.join(workdir, "flight")

    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_PLATFORMS"] = "cpu"       # a CPU tool: many processes, no chip
    witness_dir = os.path.join(workdir, "lockwitness")
    if lock_witness:
        # Every fleet process runs its locks through
        # faults.lockwitness and dumps a per-pid report at exit; the
        # summary below aggregates them into a zero-inversion /
        # zero-unguarded gate.
        env["COLEARN_LOCK_WITNESS"] = "1"
        env["COLEARN_LOCK_WITNESS_DIR"] = witness_dir
    else:
        # An operator's ambient witness env must not leak into a soak
        # that did not ask for it (the overhead would skew timings).
        env.pop("COLEARN_LOCK_WITNESS", None)
        env.pop("COLEARN_LOCK_WITNESS_DIR", None)

    fleet = _Fleet(workdir, env)
    watchdog = threading.Timer(timeout_s, fleet.kill_all)
    watchdog.daemon = True

    records: dict[int, dict] = {}
    events: list[dict] = []
    resumed = 0
    incarnations = 1
    delivered: list[dict] = []
    pending = sorted(kills, key=lambda k: (k.after_round, k.target))
    version_monotonic = True
    last_version = -1
    rc: Optional[int] = None

    try:
        watchdog.start()
        flight_flags = ["--flight-dir", flight_dir,
                        "--flight-heartbeat", "0.5"]
        health_flags = ["--health-dir", os.path.join(workdir, "health")]
        host, port = fleet.start_broker(timeout=30.0, extra=flight_flags)
        worker_cfg = (_async_config_flags(aggregations, n_workers, seed)
                      + flight_flags + health_flags)
        if n_aggregators:
            # A per-slice buffer of 1-2 devices can never clear the
            # default distinct-contributor quorum (ceil(0.5 * workers))
            # at the root — partials ship per SLICE, not per cohort.
            # Last flag wins in argparse, so the override rides at the
            # end of both role configs.
            worker_cfg += ["--min-cohort-fraction", "0"]
        for i in range(n_workers):
            fleet.start_worker(i, worker_cfg, host, port)
        # Aggregator tier: spawned before the coordinator so the
        # retained announcements are on the broker before the async
        # root's enroll_aggregators() subscribes.
        agg_cfg = worker_cfg
        for a in range(n_aggregators):
            fleet.start_aggregator(a, agg_cfg, host, port)
        coord_cfg = (_async_config_flags(aggregations, n_workers, seed,
                                         checkpoint_dir=ckpt_dir)
                     + flight_flags + health_flags)
        if n_aggregators:
            # The 1s heartbeat deadline (default 5s) keeps failover
            # detection well inside the post-kill runway of a short
            # soak; the oracle gets the same value so the runs stay
            # config-identical.
            coord_cfg += ["--num-aggregators", str(n_aggregators),
                          "--min-cohort-fraction", "0",
                          "--agg-heartbeat-timeout", "1.0"]
        if fault_plan is not None:
            plan_path = os.path.join(workdir, "fault_plan.json")
            with open(plan_path, "w") as f:
                json.dump(fault_plan, f)
            coord_cfg += ["--fault-plan", plan_path]

        def launch(resume: bool) -> subprocess.Popen:
            return fleet.start_async_coordinator(
                coord_cfg, host, port, n_workers, round_timeout,
                enroll_timeout, buffer_size, resume=resume)

        coord = launch(resume=False)
        restart_pending = False
        err_log = fleet._log_file("coordinator.err")
        while True:
            line = coord.stderr.readline()
            if line:
                err_log.write(line.encode())
                err_log.flush()
            if not line:
                coord.wait()
                if restart_pending:
                    restart_pending = False
                    incarnations += 1
                    # A fresh incarnation resumes from its checkpointed
                    # version — which may sit BELOW the dead process's
                    # last streamed record (uncommitted aggregations are
                    # lost by design).  Monotonicity restarts with it.
                    last_version = -1
                    coord = launch(resume=True)
                    continue
                rc = coord.returncode
                break
            doc = _parse_json(line.strip())
            if doc is None:
                continue
            if "event" in doc:
                events.append(doc)
                if doc["event"] == "resumed":
                    resumed += 1
                continue
            if "aggregation" not in doc:
                continue
            agg = int(doc["aggregation"])
            v = doc.get("model_version")
            if v is not None:
                if int(v) <= last_version:
                    version_monotonic = False
                last_version = int(v)
            records[agg] = doc         # last record per aggregation wins
            if log_fn is not None:
                log_fn(doc)
            while pending and pending[0].after_round <= agg:
                spec = pending.pop(0)
                kill_rec = {**dataclasses.asdict(spec),
                            "fired_after_round": agg}
                if spec.target in ("coordinator", "async-coordinator"):
                    kill_rec["pid"] = coord.pid
                    coord.send_signal(signal.SIGKILL)
                    restart_pending = True
                elif spec.target == "broker":
                    victim = fleet.broker
                    if victim is not None and victim.poll() is None:
                        kill_rec["pid"] = victim.pid
                        victim.send_signal(signal.SIGKILL)
                        victim.wait()
                    fleet.restart_broker()
                elif spec.target.startswith("aggregator:"):
                    aid = int(spec.target.split(":", 1)[1])
                    victim = fleet.aggregators.get(aid)
                    if victim is not None and victim.poll() is None:
                        kill_rec["pid"] = victim.pid
                        victim.send_signal(signal.SIGKILL)
                        victim.wait()
                    if spec.restart:
                        fleet.start_aggregator(aid, agg_cfg, host, port)
                else:
                    wid = int(spec.target.split(":", 1)[1])
                    victim = fleet.workers.get(wid)
                    if victim is not None and victim.poll() is None:
                        kill_rec["pid"] = victim.pid
                        victim.send_signal(signal.SIGKILL)
                        victim.wait()
                    if spec.restart:
                        fleet.start_worker(wid, worker_cfg, host, port)
                delivered.append(kill_rec)
    finally:
        watchdog.cancel()
        fleet.close()

    if rc is None:
        raise RuntimeError(
            f"async coordinator never exited cleanly within {timeout_s}s "
            f"(records for aggregations {sorted(records)})")

    from colearn_federated_learning_tpu.telemetry import flight as _flight

    dumps = _flight.load_flight_dumps(flight_dir)
    dumped_pids = {d.get("pid") for d in dumps if "error" not in d}
    flight_missing = sorted({k["pid"] for k in delivered if "pid" in k}
                            - dumped_pids)

    recs = [records[a] for a in sorted(records)]
    return {
        "lock_witness": (_collect_lockwitness(witness_dir)
                         if lock_witness else {"enabled": False}),
        "aggregations_run": len(recs),
        "records": recs,
        "version_monotonic": version_monotonic,
        "resumed": resumed,
        "coordinator_incarnations": incarnations,
        "kills": delivered,
        "flight_dumps": len(dumped_pids),
        "flight_missing": flight_missing,
        "events": events,
        "exit_code": rc,
        "workdir": workdir,
    }


def _collect_lockwitness(witness_dir: str) -> dict:
    """Merge the fleet's per-pid ``lockwitness-*.json`` dumps into one
    gateable summary: report count, total inversions/unguarded (with the
    offending records inlined for the operator), and the acquire volume
    that vouches the witness actually saw traffic."""
    reports = []
    skipped = 0
    if os.path.isdir(witness_dir):
        for name in sorted(os.listdir(witness_dir)):
            if not (name.startswith("lockwitness-")
                    and name.endswith(".json")):
                continue
            try:
                with open(os.path.join(witness_dir, name)) as f:
                    reports.append(json.load(f))
            except (OSError, ValueError):
                # An unparseable dump (process died mid-write) is not a
                # witnessed bug; the skipped count exposes the gap.
                skipped += 1
    inversions = [inv for r in reports for inv in r.get("inversions", [])]
    unguarded = [u for r in reports for u in r.get("unguarded", [])]
    return {
        "enabled": True,
        "reports": len(reports),
        "reports_unparseable": skipped,
        "acquires": sum(int(r.get("acquires", 0)) for r in reports),
        "guarded_ops": sum(int(r.get("guarded_ops", 0)) for r in reports),
        "inversions": len(inversions),
        "unguarded": len(unguarded),
        "inversion_records": inversions,
        "unguarded_records": unguarded,
    }


def _tail_loss(records: list[dict], n: int = 3) -> float:
    """Mean train loss over the last ``n`` aggregations — buffered-async
    losses are thread-timing noisy aggregation to aggregation, so the
    gate compares smoothed tails, not single records."""
    import math as _math

    tail = [float(r["train_loss"]) for r in records
            if "train_loss" in r
            and _math.isfinite(float(r["train_loss"]))][-n:]
    return sum(tail) / len(tail) if tail else float("inf")


def run_async_soak(
    aggregations: int = 6,
    n_workers: int = 3,
    buffer_size: int = 2,
    workdir: Optional[str] = None,
    round_timeout: float = 120.0,
    enroll_timeout: float = 90.0,
    timeout_s: float = 600.0,
    kill: bool = True,
    seed: int = 0,
    loss_tol: float = 0.75,
    log_fn: Optional[Callable[[dict], None]] = None,
    lock_witness: bool = False,
) -> dict:
    """Buffered-async chaos gate: SIGKILL the async coordinator
    mid-aggregation, relaunch with ``--resume``, and hold the recovered
    run to the invariants a lost buffer must not break.

    Two full subprocess federations, identical config and seed:

    - **faulted** — the async coordinator is SIGKILLed the moment
      aggregation ``aggregations // 2 - 1``'s record streams (so the
      signal lands mid-aggregation with dispatcher pumps in flight,
      buffered updates unfolded, the version condition mid-notify), then
      relaunched with ``--resume``; a count-bounded client-site
      :class:`~.plan.FaultPlan` also rides on its dispatcher pumps;
    - **baseline** — the same federation, kill-free and fault-free.

    Gates (``colearn chaos --async``):

    - *version monotonicity* — within each coordinator incarnation the
      streamed ``model_version`` strictly increases; resume restarts
      from the checkpointed version and uncommitted aggregations are
      re-run, never replayed out of order;
    - *no RDP double-charge* — replaying each final aggregation record's
      ``dp_z_eff`` into a fresh accountant must land on the final
      record's ``dp_epsilon``: the resumed coordinator rebuilt its
      budget from the checkpointed history exactly once;
    - *loss parity* — the faulted run's tail train loss stays within
      ``loss_tol`` of the kill-free baseline's (async losses are
      thread-timing noisy; the tolerance covers scheduling, not
      divergence);
    - *attribution* — the SIGKILLed pid left a parseable flight dump
      whose postmortem names the coordinator role, the health ledgers
      survive the kill, and the injected pump faults show up as
      per-device retry counts in the ledger."""
    if aggregations < 4:
        raise ValueError(
            f"async soak needs >= 4 aggregations so the kill lands after "
            f"a committed checkpoint, got {aggregations}")
    workdir = workdir or tempfile.mkdtemp(prefix="colearn_asyncsoak_")
    os.makedirs(workdir, exist_ok=True)
    kills = ([KillSpec("async-coordinator",
                       after_round=max(1, aggregations // 2 - 1))]
             if kill else [])

    faulted = _run_async_fleet(
        aggregations=aggregations, n_workers=n_workers,
        buffer_size=buffer_size, kills=kills,
        workdir=os.path.join(workdir, "faulted"),
        round_timeout=round_timeout, enroll_timeout=enroll_timeout,
        timeout_s=timeout_s, seed=seed,
        fault_plan=_async_fault_plan() if kill else None, log_fn=log_fn,
        lock_witness=lock_witness)
    baseline = _run_async_fleet(
        aggregations=aggregations, n_workers=n_workers,
        buffer_size=buffer_size, kills=[],
        workdir=os.path.join(workdir, "baseline"),
        round_timeout=round_timeout, enroll_timeout=enroll_timeout,
        timeout_s=timeout_s, seed=seed, fault_plan=None, log_fn=log_fn,
        lock_witness=lock_witness)

    # RDP replay: the deduplicated record stream IS the final
    # coordinator's history (LAST record per aggregation wins, exactly
    # like the checkpointed history the resumed incarnation extended).
    # Re-deriving epsilon from the per-record realized multipliers must
    # land on the final record's figure — a double-charged resume (or a
    # restore that failed to reset) diverges here.
    from colearn_federated_learning_tpu.privacy.accountant import (
        RdpAccountant,
    )

    acct = RdpAccountant(noise_multiplier=_ASYNC_DP_NOISE,
                         sampling_rate=1.0, delta=_ASYNC_DP_DELTA)
    final_eps = None
    for rec in faulted["records"]:
        if "dp_z_eff" in rec:
            acct.step(1, sampling_rate=1.0,
                      noise_multiplier=float(rec["dp_z_eff"]))
        if "dp_epsilon" in rec:
            final_eps = float(rec["dp_epsilon"])
    replayed_eps = acct.epsilon()
    import math as _math

    dp_replay_ok = (final_eps is not None
                    and _math.isfinite(final_eps)
                    and _math.isfinite(replayed_eps)
                    and abs(replayed_eps - final_eps)
                    <= 1e-6 * max(1.0, abs(final_eps)))

    final_loss = _tail_loss(faulted["records"])
    baseline_loss = _tail_loss(baseline["records"])
    loss_gap = abs(final_loss - baseline_loss)
    loss_gap_ok = _math.isfinite(loss_gap) and loss_gap <= loss_tol

    # Postmortem attribution: the SIGKILLed async coordinator's black
    # box must parse and the merged report must name the coordinator
    # role for its pid.
    from colearn_federated_learning_tpu.telemetry import flight as _flight

    killed_pids = {k["pid"] for k in faulted["kills"] if "pid" in k}
    if killed_pids:
        dumps = _flight.load_flight_dumps(
            os.path.join(workdir, "faulted", "flight"))
        report = _flight.postmortem_report(dumps)
        attributed = any(
            p.get("pid") in killed_pids
            and str(p.get("role", "")) == "coordinator"
            for p in report.get("processes", []))
    else:
        attributed = not kill

    # Health-ledger durability + fault attribution: the ledgers must
    # survive the SIGKILL (parse, non-empty), and with the fault plan
    # armed at least one device must carry attributed retries — the
    # injected pump flaps landed in the per-device ledger, not just a
    # process-local counter that died with its incarnation.
    from colearn_federated_learning_tpu.telemetry import health as _health

    try:
        devices = _health.load_health(
            os.path.join(workdir, "faulted", "health"))
    except ValueError:
        devices = {}
    health_ok = bool(devices)
    fault_retries = sum(int(h.counts.get("retry", 0))
                        for h in devices.values())
    faults_attributed = (not kill) or fault_retries >= 1

    return {
        "exit_code": faulted["exit_code"],
        "baseline_exit_code": baseline["exit_code"],
        "aggregations_run": faulted["aggregations_run"],
        "baseline_aggregations_run": baseline["aggregations_run"],
        "version_monotonic": (faulted["version_monotonic"]
                              and baseline["version_monotonic"]),
        "resumed": faulted["resumed"],
        "coordinator_incarnations": faulted["coordinator_incarnations"],
        "dp_replay_ok": dp_replay_ok,
        "dp_epsilon": final_eps,
        "dp_epsilon_replayed": replayed_eps,
        "final_loss": final_loss,
        "baseline_final_loss": baseline_loss,
        "loss_gap": loss_gap,
        "loss_gap_ok": loss_gap_ok,
        "postmortem_attributed": attributed,
        "health_ledger_ok": health_ok,
        "health_devices": len(devices),
        "fault_retries": fault_retries,
        "faults_attributed": faults_attributed,
        "flight_missing": faulted["flight_missing"],
        "kills": faulted["kills"],
        "records": faulted["records"],
        "lock_witness": _merge_lockwitness(faulted["lock_witness"],
                                           baseline["lock_witness"]),
        "workdir": workdir,
    }


def _merge_lockwitness(*parts: dict) -> dict:
    """Fold the per-fleet witness summaries (faulted + baseline/oracle)
    into the one entry the chaos gate reads."""
    if not any(p.get("enabled") for p in parts):
        return {"enabled": False}
    merged = {"enabled": True, "reports": 0, "acquires": 0,
              "guarded_ops": 0, "inversions": 0, "unguarded": 0,
              "inversion_records": [], "unguarded_records": []}
    for p in parts:
        if not p.get("enabled"):
            continue
        for k in ("reports", "acquires", "guarded_ops",
                  "inversions", "unguarded"):
            merged[k] += int(p.get(k, 0))
        merged["inversion_records"] += list(p.get("inversion_records", []))
        merged["unguarded_records"] += list(p.get("unguarded_records", []))
    return merged


def run_tree_async_soak(
    aggregations: int = 6,
    n_workers: int = 3,
    buffer_size: int = 2,
    workdir: Optional[str] = None,
    round_timeout: float = 120.0,
    enroll_timeout: float = 90.0,
    timeout_s: float = 900.0,
    kill: bool = True,
    seed: int = 0,
    loss_tol: float = 0.75,
    log_fn: Optional[Callable[[dict], None]] = None,
    lock_witness: bool = False,
) -> dict:
    """Tree-async chaos gate: buffered-async THROUGH the aggregator
    tree, with an aggregator SIGKILLed mid-aggregation (and left dead)
    plus a broker kill-and-rebind one aggregation later.

    Two full subprocess federations, identical config and seed, both
    running buffered-async through 2 per-slice aggregator buffers:

    - **faulted** — aggregator 0 dies the moment aggregation
      ``aggregations // 2 - 1``'s record streams (mid-aggregation:
      dispatcher pumps in flight, its buffer part-staged) and STAYS
      dead — the root must sticky-dead its address and re-home the
      in-flight contributions of its slice onto aggregator 1 without
      folding any of them twice; one aggregation later the broker is
      SIGKILLed and rebinds its original port (worker re-enrollment
      watchdogs + the root's announcement re-subscribe must heal);
    - **oracle** — the same tree federation, kill-free.

    Gates (``colearn chaos --tree-async``):

    - *loss parity* — the faulted run's tail train loss stays within
      ``loss_tol`` of the kill-free tree oracle's;
    - *zero double-folds* — every dedup key in the record stream's
      ``folded_keys`` lists is globally unique across the run: a
      re-homed contribution folded exactly once, on exactly one
      aggregator (``double_folds`` must be 0);
    - *failover fired* — summed ``agg_failovers`` >= 1 with ``kill``;
    - *re-home attribution* — every device named in a record's
      ``rehomed_devices`` carries ``rehomed >= 1`` in the health
      ledger: the ledger tells the operator WHO rode through the
      failover, not just that one happened;
    - *version monotonicity*, flight-dump coverage of every SIGKILLed
      pid, postmortem attribution of the dead aggregator, and
      health-ledger durability, as in the flat async soak."""
    if aggregations < 4:
        raise ValueError(
            f"tree-async soak needs >= 4 aggregations so the kills land "
            f"inside the run, got {aggregations}")
    workdir = workdir or tempfile.mkdtemp(prefix="colearn_treeasync_")
    os.makedirs(workdir, exist_ok=True)
    # Kill EARLY (after ~a third of the run) so the post-kill runway is
    # long enough for bounded-deadline detection to fire, the in-flight
    # slice-0 contributions to re-home, and the re-homed partials to
    # fold into later records — all before the root hits its target.
    cut = max(1, aggregations // 3)
    kills = ([KillSpec("aggregator:0", after_round=cut, restart=False),
              KillSpec("broker", after_round=min(cut + 2,
                                                 aggregations - 1))]
             if kill else [])

    faulted = _run_async_fleet(
        aggregations=aggregations, n_workers=n_workers,
        buffer_size=buffer_size, kills=kills,
        workdir=os.path.join(workdir, "faulted"),
        round_timeout=round_timeout, enroll_timeout=enroll_timeout,
        timeout_s=timeout_s, seed=seed, n_aggregators=2,
        fault_plan=None, log_fn=log_fn, lock_witness=lock_witness)
    oracle = _run_async_fleet(
        aggregations=aggregations, n_workers=n_workers,
        buffer_size=buffer_size, kills=[],
        workdir=os.path.join(workdir, "oracle"),
        round_timeout=round_timeout, enroll_timeout=enroll_timeout,
        timeout_s=timeout_s, seed=seed, n_aggregators=2,
        fault_plan=None, log_fn=log_fn, lock_witness=lock_witness)

    import math as _math

    final_loss = _tail_loss(faulted["records"])
    oracle_loss = _tail_loss(oracle["records"])
    loss_gap = abs(final_loss - oracle_loss)
    loss_gap_ok = _math.isfinite(loss_gap) and loss_gap <= loss_tol

    # Double-fold audit: each aggregation record carries the dedup keys
    # (``version@device``) its folded partial was built from.  A key
    # appearing in two records means one contribution reached the model
    # twice — the exact failure mode re-home-with-ack-on-receipt
    # exists to prevent.  Records are deduplicated by aggregation index
    # (LAST wins), so a resumed re-run never false-positives here.
    seen_keys: set = set()
    double_folds = 0
    for rec in faulted["records"]:
        for key in rec.get("folded_keys", []):
            if key in seen_keys:
                double_folds += 1
            seen_keys.add(key)

    agg_failovers = sum(int(r.get("agg_failovers", 0))
                        for r in faulted["records"])
    failover_fired = (not kill) or agg_failovers >= 1
    rehomed_devices = sorted({str(d) for r in faulted["records"]
                              for d in r.get("rehomed_devices", [])})

    # Postmortem: the dead aggregator's black box must parse and the
    # merged report must name the aggregator role for its pid.
    from colearn_federated_learning_tpu.telemetry import flight as _flight

    killed_pids = {k["pid"] for k in faulted["kills"] if "pid" in k}
    if killed_pids:
        dumps = _flight.load_flight_dumps(
            os.path.join(workdir, "faulted", "flight"))
        report = _flight.postmortem_report(dumps)
        agg_attributed = any(
            p.get("pid") in killed_pids
            and str(p.get("role", "")).startswith("aggregator")
            for p in report.get("processes", []))
    else:
        agg_attributed = not kill

    # Health-ledger attribution of the re-home: durability first (the
    # ledgers must parse and be non-empty), then the re-home trail —
    # every device the record stream says was re-homed must carry a
    # ``rehomed`` count in the merged ledger.
    from colearn_federated_learning_tpu.telemetry import health as _health

    try:
        devices = _health.load_health(
            os.path.join(workdir, "faulted", "health"))
    except ValueError:
        devices = {}
    health_ok = bool(devices)
    ledger_rehomed = {d for d, h in devices.items()
                     if int(h.counts.get("rehomed", 0)) >= 1}
    rehomed_attributed = ((not kill) or
                          (bool(rehomed_devices)
                           and set(rehomed_devices) <= ledger_rehomed))

    return {
        "exit_code": faulted["exit_code"],
        "oracle_exit_code": oracle["exit_code"],
        "aggregations_run": faulted["aggregations_run"],
        "oracle_aggregations_run": oracle["aggregations_run"],
        "version_monotonic": (faulted["version_monotonic"]
                              and oracle["version_monotonic"]),
        "final_loss": final_loss,
        "oracle_final_loss": oracle_loss,
        "loss_gap": loss_gap,
        "loss_gap_ok": loss_gap_ok,
        "double_folds": double_folds,
        "folded_keys_total": len(seen_keys),
        "agg_failovers": agg_failovers,
        "failover_fired": failover_fired,
        "rehomed_devices": rehomed_devices,
        "rehomed_attributed": rehomed_attributed,
        "postmortem_attributed": agg_attributed,
        "health_ledger_ok": health_ok,
        "health_devices": len(devices),
        "flight_missing": faulted["flight_missing"],
        "kills": faulted["kills"],
        "records": faulted["records"],
        "lock_witness": _merge_lockwitness(faulted["lock_witness"],
                                           oracle["lock_witness"]),
        "workdir": workdir,
    }

# --------------------------------------------------- streaming-ckpt soak --

def _ckpt_fault_plan(slow_ms: int) -> dict:
    """``slow_io`` on every per-shard checkpoint write: each shard file
    costs an extra ``slow_ms`` before its bytes land, stretching the
    window between the first shard commit and the manifest commit so the
    save watcher's SIGKILL deterministically lands INSIDE a save."""
    return {"seed": 0, "faults": [
        {"kind": "slow_io", "device_id": "*", "round": -1, "op": "shard",
         "ms": slow_ms, "count": 0, "site": "server", "hop": "shard"},
    ]}


def _ckpt_gen_entries(ckpt_dir: str) -> list[str]:
    try:
        names = os.listdir(ckpt_dir)
    except OSError:
        return []
    return sorted(os.path.join(ckpt_dir, n) for n in names
                  if n.startswith("gen_"))


def _ckpt_has_committed(ckpt_dir: str) -> bool:
    return any(os.path.exists(os.path.join(g, "manifest.json"))
               for g in _ckpt_gen_entries(ckpt_dir))


def _ckpt_in_progress(ckpt_dir: str) -> Optional[str]:
    """The newest generation directory that has shard files on disk but
    no manifest — a save in flight (or a dead one the next restore will
    fall through)."""
    for g in reversed(_ckpt_gen_entries(ckpt_dir)):
        if os.path.exists(os.path.join(g, "manifest.json")):
            continue
        try:
            names = os.listdir(g)
        except OSError:  # colearn: noqa(CL003): poll race — the dir the
            continue     # coordinator is pruning mid-scan simply isn't
                         # an in-progress save; the watcher re-polls.
        if any(n.startswith("shard_") and n.endswith(".npz")
               for n in names):
            return g
    return None


def _run_ckpt_fleet(
    rounds: int,
    n_workers: int,
    workdir: str,
    round_timeout: float,
    enroll_timeout: float,
    timeout_s: float,
    seed: int,
    tp_size: int,
    resume_tp_size: int,
    kill_during_save: bool,
    fault_plan: Optional[dict] = None,
    start_resumed: bool = False,
    ckpt_dir: Optional[str] = None,
    log_fn: Optional[Callable[[dict], None]] = None,
) -> dict:
    """One streaming-checkpoint fleet (broker + N workers + sync
    coordinator with ``--ckpt-stream``).  Unlike the round-keyed kill
    loop, the kill here is FILESYSTEM-keyed: with ``kill_during_save`` a
    watcher thread polls the checkpoint directory and SIGKILLs the
    coordinator the moment a generation has shard files on disk but no
    manifest — i.e. mid-save, after at least one earlier generation
    committed (so the resume has something to fall back to).  Right
    after the kill the watcher snapshots the last COMMITTED generation
    (step + content digest) via
    :func:`~..ckpt.streaming.load_generation_host`; the relaunched
    ``--resume`` coordinator (at ``resume_tp_size``) must restore
    exactly that.  ``start_resumed`` launches the FIRST coordinator with
    ``--resume`` against an existing ``ckpt_dir`` — the kill-free
    cross-tp smoke leg."""
    os.makedirs(workdir, exist_ok=True)
    ckpt_dir = ckpt_dir or os.path.join(workdir, "ckpt")
    flight_dir = os.path.join(workdir, "flight")

    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env["JAX_PLATFORMS"] = "cpu"       # a CPU tool: many processes, no chip
    # The coordinator's sharded-server placement needs >= tp_size XLA
    # host devices; match the test suite's 8-device CPU layout (workers
    # ignore the extra devices).
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()

    fleet = _Fleet(workdir, env)
    watchdog = threading.Timer(timeout_s, fleet.kill_all)
    watchdog.daemon = True

    records: dict[int, dict] = {}
    events: list[dict] = []
    per_client: dict = {}
    resumed = 0
    incarnations = 1
    resume_event: Optional[dict] = None
    rc: Optional[int] = None
    holder: dict = {"coord": None, "restart_pending": False, "stop": False}
    killed: dict = {}

    def watch() -> None:
        from colearn_federated_learning_tpu.ckpt.streaming import (
            load_generation_host,
        )

        # Arm only once a generation has COMMITTED: a kill during the
        # very first save would leave nothing to fall back to, and the
        # gate is "lose at most the uncommitted generation", not "lose
        # the run".
        while not holder["stop"] and not _ckpt_has_committed(ckpt_dir):
            time.sleep(0.02)
        prog = None
        while not holder["stop"]:
            prog = _ckpt_in_progress(ckpt_dir)
            if prog:
                break
            time.sleep(0.01)
        coord = holder["coord"]
        if holder["stop"] or coord is None or prog is None:
            return
        holder["restart_pending"] = True
        killed["pid"] = coord.pid
        killed["gen"] = os.path.basename(prog)
        coord.send_signal(signal.SIGKILL)
        coord.wait()
        # The process is dead and the resume incarnation is seconds
        # away, so the directory is frozen: record what the next
        # restore MUST come back with.
        killed["mid_save"] = not os.path.exists(
            os.path.join(prog, "manifest.json"))
        try:
            _, step, digest = load_generation_host(ckpt_dir)
            killed["committed_step"] = step
            killed["digest"] = digest
        except FileNotFoundError:
            killed["committed_step"] = None
            killed["digest"] = None

    watcher = (threading.Thread(target=watch, daemon=True)
               if kill_during_save else None)

    try:
        watchdog.start()
        flight_flags = ["--flight-dir", flight_dir,
                        "--flight-heartbeat", "0.5"]
        host, port = fleet.start_broker(timeout=30.0, extra=flight_flags)
        worker_cfg = _config_flags(rounds, n_workers, seed) + flight_flags
        for i in range(n_workers):
            fleet.start_worker(i, worker_cfg, host, port)
        coord_cfg = (_config_flags(rounds, n_workers, seed,
                                   checkpoint_dir=ckpt_dir)
                     + ["--ckpt-stream"] + flight_flags)
        if fault_plan is not None:
            plan_path = os.path.join(workdir, "fault_plan.json")
            with open(plan_path, "w") as f:
                json.dump(fault_plan, f)
            coord_cfg += ["--fault-plan", plan_path]

        def launch(resume: bool) -> subprocess.Popen:
            tp = resume_tp_size if resume else tp_size
            c = fleet.start_coordinator(
                coord_cfg + ["--tp-size", str(tp)], host, port, n_workers,
                round_timeout, enroll_timeout, resume=resume)
            holder["coord"] = c
            return c

        coord = launch(resume=start_resumed)
        if watcher is not None:
            watcher.start()
        err_log = fleet._log_file("coordinator.err")
        while True:
            line = coord.stderr.readline()
            if line:
                err_log.write(line.encode())
                err_log.flush()
            if not line:
                coord.wait()
                if holder["restart_pending"]:
                    holder["restart_pending"] = False
                    incarnations += 1
                    coord = launch(resume=True)
                    continue
                rc = coord.returncode
                break
            doc = _parse_json(line.strip())
            if doc is None:
                continue
            if "event" in doc:
                events.append(doc)
                if doc["event"] == "resumed":
                    resumed += 1
                    resume_event = doc
                continue
            if "num_clients_evaluated" in doc:
                per_client = doc
                continue
            if "round" not in doc:
                continue
            records[int(doc["round"])] = doc
            if log_fn is not None:
                log_fn(doc)
    finally:
        holder["stop"] = True
        watchdog.cancel()
        fleet.close()
        if watcher is not None and watcher.is_alive():
            watcher.join(timeout=5.0)

    if rc is None:
        raise RuntimeError(
            f"coordinator never exited cleanly within {timeout_s}s "
            f"(records for rounds {sorted(records)})")

    from colearn_federated_learning_tpu.telemetry import flight as _flight

    dumps = _flight.load_flight_dumps(flight_dir)
    dumped_pids = {d.get("pid") for d in dumps if "error" not in d}
    flight_missing = sorted(({killed["pid"]} if "pid" in killed else set())
                            - dumped_pids)

    recs = [records[r] for r in sorted(records)]
    return {
        "rounds_run": len(recs),
        "records": recs,
        "weighted_acc": per_client.get("weighted_acc"),
        "resumed": resumed,
        "resume_event": resume_event,
        "coordinator_incarnations": incarnations,
        "kill": killed,
        "flight_dumps": len(dumped_pids),
        "flight_missing": flight_missing,
        "events": events,
        "exit_code": rc,
        "ckpt_dir": ckpt_dir,
        "workdir": workdir,
    }


def run_ckpt_soak(
    rounds: int = 4,
    n_workers: int = 2,
    workdir: Optional[str] = None,
    round_timeout: float = 120.0,
    enroll_timeout: float = 90.0,
    timeout_s: float = 600.0,
    kill: bool = True,
    seed: int = 0,
    loss_tol: float = 0.75,
    tp_size: int = 2,
    resume_tp_size: int = 1,
    slow_ms: int = 300,
    log_fn: Optional[Callable[[dict], None]] = None,
) -> dict:
    """Streaming-checkpoint chaos gate (``colearn chaos --ckpt``).

    **Kill leg** (``kill=True``): a tp=``tp_size`` federation saves a
    shard-native streaming checkpoint every round under an injected
    ``slow_io`` plan; a filesystem watcher SIGKILLs the coordinator the
    moment a save is mid-flight (shard files on disk, manifest not yet
    committed) AFTER at least one generation committed.  The relaunched
    ``--resume`` coordinator comes back at tp=``resume_tp_size`` — the
    cross-tp re-shard leg — and the gate holds:

    - *atomicity* — the kill landed mid-save (``killed_mid_save``) and
      the resume restored exactly the last COMMITTED generation: the
      resumed round equals the step the watcher snapshotted at kill
      time, i.e. at most the one uncommitted generation was lost;
    - *bitwise restore* — the resume event's ``ckpt_digest`` (sha256
      over the restored full-leaf bytes in flatten order) equals the
      digest :func:`~..ckpt.streaming.load_generation_host` computed
      from the on-disk generation at kill time, across the tp change
      (``resharded >= 1`` when ``resume_tp_size != tp_size``);
    - *loss parity* — tail train loss within ``loss_tol`` of a same-seed
      kill-free tp=``resume_tp_size`` oracle federation;
    - *attribution* — the SIGKILLed pid left a parseable flight dump
      whose postmortem names the coordinator role.

    **Smoke leg** (``kill=False``): a kill-free tp=``tp_size`` run to
    completion, then a fresh fleet resumes the SAME checkpoint directory
    at tp=``resume_tp_size`` with zero rounds left — the resume event's
    digest must match the harness's independent
    ``load_generation_host`` digest of the final generation, bitwise,
    across the re-shard."""
    if rounds < 3:
        raise ValueError(
            f"ckpt soak needs >= 3 rounds so the mid-save kill lands "
            f"after a committed generation, got {rounds}")
    workdir = workdir or tempfile.mkdtemp(prefix="colearn_ckptsoak_")
    os.makedirs(workdir, exist_ok=True)
    reshard = tp_size != resume_tp_size

    if not kill:
        first = _run_ckpt_fleet(
            rounds, n_workers, os.path.join(workdir, "save"),
            round_timeout, enroll_timeout, timeout_s, seed,
            tp_size=tp_size, resume_tp_size=tp_size,
            kill_during_save=False, log_fn=log_fn)
        from colearn_federated_learning_tpu.ckpt.streaming import (
            load_generation_host,
        )

        _, step, digest = load_generation_host(first["ckpt_dir"])
        second = _run_ckpt_fleet(
            rounds, n_workers, os.path.join(workdir, "resume"),
            round_timeout, enroll_timeout, timeout_s, seed,
            tp_size=resume_tp_size, resume_tp_size=resume_tp_size,
            kill_during_save=False, start_resumed=True,
            ckpt_dir=first["ckpt_dir"], log_fn=log_fn)
        ev = second["resume_event"] or {}
        return {
            "mode": "smoke",
            "exit_code": first["exit_code"],
            "resume_exit_code": second["exit_code"],
            "rounds_run": first["rounds_run"],
            "committed_step": step,
            "save_digest": digest,
            "resume_digest": ev.get("ckpt_digest"),
            "resume_round": ev.get("round"),
            "resume_round_ok": ev.get("round") == step,
            "digest_ok": (digest is not None
                          and ev.get("ckpt_digest") == digest),
            "resharded_resumes": int(ev.get("resharded", 0) or 0),
            "reshard_ok": ((not reshard)
                           or int(ev.get("resharded", 0) or 0) >= 1),
            "records": first["records"],
            "workdir": workdir,
        }

    faulted = _run_ckpt_fleet(
        rounds, n_workers, os.path.join(workdir, "faulted"),
        round_timeout, enroll_timeout, timeout_s, seed,
        tp_size=tp_size, resume_tp_size=resume_tp_size,
        kill_during_save=True, fault_plan=_ckpt_fault_plan(slow_ms),
        log_fn=log_fn)
    oracle = _run_ckpt_fleet(
        rounds, n_workers, os.path.join(workdir, "oracle"),
        round_timeout, enroll_timeout, timeout_s, seed,
        tp_size=resume_tp_size, resume_tp_size=resume_tp_size,
        kill_during_save=False, log_fn=log_fn)

    import math as _math

    ev = faulted["resume_event"] or {}
    killed = faulted["kill"]
    committed = killed.get("committed_step")
    final_loss = _tail_loss(faulted["records"])
    oracle_loss = _tail_loss(oracle["records"])
    loss_gap = abs(final_loss - oracle_loss)

    from colearn_federated_learning_tpu.telemetry import flight as _flight

    attributed = False
    if "pid" in killed:
        dumps = _flight.load_flight_dumps(
            os.path.join(workdir, "faulted", "flight"))
        report = _flight.postmortem_report(dumps)
        attributed = any(
            p.get("pid") == killed["pid"]
            and str(p.get("role", "")) == "coordinator"
            for p in report.get("processes", []))

    return {
        "mode": "kill",
        "exit_code": faulted["exit_code"],
        "oracle_exit_code": oracle["exit_code"],
        "rounds_run": faulted["rounds_run"],
        "oracle_rounds_run": oracle["rounds_run"],
        "killed_mid_save": bool(killed.get("mid_save")),
        "killed_gen": killed.get("gen"),
        "committed_step": committed,
        "kill_digest": killed.get("digest"),
        "resume_digest": ev.get("ckpt_digest"),
        "resume_round": ev.get("round"),
        "resume_round_ok": (committed is not None
                            and ev.get("round") == committed),
        "digest_ok": (killed.get("digest") is not None
                      and ev.get("ckpt_digest") == killed["digest"]),
        "resharded_resumes": int(ev.get("resharded", 0) or 0),
        "reshard_ok": ((not reshard)
                       or int(ev.get("resharded", 0) or 0) >= 1),
        "resumed": faulted["resumed"],
        "coordinator_incarnations": faulted["coordinator_incarnations"],
        "final_loss": final_loss,
        "oracle_final_loss": oracle_loss,
        "loss_gap": loss_gap,
        "loss_gap_ok": _math.isfinite(loss_gap) and loss_gap <= loss_tol,
        "postmortem_attributed": attributed,
        "flight_missing": faulted["flight_missing"],
        "kill": killed,
        "records": faulted["records"],
        "workdir": workdir,
    }
