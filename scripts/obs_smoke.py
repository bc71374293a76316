#!/usr/bin/env python3
"""Observability-plane smoke: one tiny real federation, every plane hit.

Spawns broker + 2 workers + a coordinator (real subprocesses on real
ports, CPU) with the full observability plane opted in — flight recorder
on a fast heartbeat, Prometheus endpoint on an ephemeral port, JSONL
event stream — then:

- scrapes ``/metrics`` mid-run and validates every line against the
  Prometheus text-exposition grammar;
- captures ``/snapshot.json`` mid-run and feeds it to ``colearn top
  --once`` (replayed from a local server after the run — the CLI's
  interpreter start-up is slower than the 3-round federation, so
  pointing it at the live coordinator would race its exit);
- SIGKILLs a worker mid-run and asserts it left a parseable flight dump
  (the heartbeat-survivability contract);
- asserts the event stream carries the start event and one event per
  round;
- feeds the flight dir through ``colearn postmortem``.

A second **tree phase** then runs the same federation through a
2-aggregator tier (``--num-aggregators 2``) with ``--trace-dir`` and
``--health-dir`` opted in and asserts the fleet-health plane end to end:

- the coordinator's Chrome trace holds ONE stitched round trace whose
  spans cover all three tiers (coordinator -> aggregator-0/1 slice
  folds -> worker train spans) with intact parent links;
- the per-device health ledger is durable and non-empty (``colearn
  health`` would render it);
- the mid-run scrape carries LABELED histogram samples
  (``fed_phase_time_s{phase=...}``) that satisfy the same exposition
  grammar.

A third **async phase** (also runnable alone: ``obs_smoke.py async``,
the CI ``async-soak`` job's observability step) runs a REAL buffered-
async federation (broker + 3 workers + ``coordinate --async-buffer 2
--async-observe``) and asserts the staleness observatory end to end:

- the mid-run scrape carries the labeled staleness histogram
  (``colearn_async_staleness{...outcome=...}``) and the arrival-rate
  gauge, all passing the exposition grammar;
- the coordinator's Chrome trace stitches dispatch -> train -> fold per
  update: every ``fold_update`` span is parented on its update's
  ``dispatch_train`` context, carries τ (``tau``) in its span args, and
  shares a trace with the worker-side ``worker.train`` span.

A fourth **learning phase** runs the same federation under
``--learn-observe`` (the convergence observatory) and asserts its
end-to-end contract:

- the mid-run scrape carries the ``learn_*`` instruments — the
  update-norm gauge and the labeled trend census
  (``colearn_learn_trend_total{trend=...}``) — under the same exposition
  grammar;
- the committed event stream carries the ``conv_*`` trail: one
  ``conv_update_norm``/``conv_trend`` signal per round, with
  ``conv_cos_prev`` absent on the first round (undefined) and present
  on every later one.

Exit 0 only if every check passes.  This is the CI ``obs-smoke`` job;
the SLO sentinel gate (``colearn sentinel``) runs as its own CI step.
Pass phase names (``classic``, ``tree``, ``async``, ``learning``) as
argv to run a subset.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

_CLI = "colearn_federated_learning_tpu.cli"
ROUNDS = 3
N_WORKERS = 2

# Prometheus text exposition 0.0.4: comment lines or `name{labels} value`.
_PROM_LINE = re.compile(
    r"^(# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* .*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.eE+naif-]+)$")


def _config_flags(n_clients: int = N_WORKERS) -> list[str]:
    return ["--config", "mnist_mlp_fedavg", "--backend", "cpu",
            "--dataset", "mnist_tiny", "--partition", "iid",
            "--num-clients", str(n_clients), "--rounds", str(ROUNDS),
            "--cohort-size", "0", "--local-steps", "2",
            "--batch-size", "16", "--min-cohort-fraction", "0.5",
            "--evict-after", "2", "--seed", "0"]


def run_tree_phase(check, env: dict) -> None:
    """2-aggregator federation: stitched trace + health ledger + labeled
    histograms (the fleet-health plane's end-to-end contract)."""
    workdir = tempfile.mkdtemp(prefix="colearn_obs_tree_")
    trace_dir = os.path.join(workdir, "trace")
    health_dir = os.path.join(workdir, "health")
    cfg = _config_flags() + ["--health-dir", health_dir]
    procs: list[subprocess.Popen] = []

    def spawn(args: list[str], **kw) -> subprocess.Popen:
        p = subprocess.Popen([sys.executable, "-m", _CLI, *args],
                             env=env, **kw)
        procs.append(p)
        return p

    try:
        broker = spawn(["broker"], stdout=subprocess.PIPE, text=True)
        addr = json.loads(broker.stdout.readline())
        host, port = addr["host"], str(addr["port"])
        for i in range(N_WORKERS):
            log = open(os.path.join(workdir, f"worker{i}.log"), "ab")
            spawn(["worker", *cfg, "--client-id", str(i),
                   "--broker-host", host, "--broker-port", port],
                  stdout=log, stderr=log)
        for a in range(2):
            log = open(os.path.join(workdir, f"aggregator{a}.log"), "ab")
            spawn(["aggregator", *cfg, "--agg-id", str(a),
                   "--broker-host", host, "--broker-port", port],
                  stdout=log, stderr=log)
        coord = spawn(
            ["coordinate", *cfg, "--num-aggregators", "2",
             "--trace-dir", trace_dir, "--metrics-port", "0",
             "--broker-host", host, "--broker-port", port,
             "--min-devices", str(N_WORKERS), "--round-timeout", "30",
             "--enroll-timeout", "90", "--no-evaluator"],
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)

        metrics_port = None
        scraped = False
        for line in coord.stderr:
            try:
                doc = json.loads(line.strip())
            except json.JSONDecodeError:
                continue
            if doc.get("event") == "metrics_port":
                metrics_port = int(doc["port"])
            if "round" in doc and not scraped and metrics_port:
                scraped = True
                url = f"http://127.0.0.1:{metrics_port}/metrics"
                text = urllib.request.urlopen(url, timeout=10) \
                    .read().decode("utf-8")
                lines = [ln for ln in text.splitlines() if ln]
                bad = [ln for ln in lines if not _PROM_LINE.match(ln)]
                check(not bad,
                      f"tree scrape matches the exposition grammar "
                      f"(bad: {bad[:3]})")
                labeled_hist = [
                    ln for ln in lines
                    if ln.startswith("colearn_fed_phase_time_s{")
                    and "quantile=" in ln and "phase=" in ln]
                check(bool(labeled_hist),
                      "scrape carries LABELED histogram samples "
                      "(fed_phase_time_s{phase=...})")
        rc = coord.wait(timeout=180)
        check(rc == 0, f"tree coordinator exited 0 (got {rc})")

        from colearn_federated_learning_tpu import telemetry

        # One stitched round trace: coordinator, BOTH aggregator slice
        # folds, and worker train spans, linked parent -> child.
        traces = ([os.path.join(trace_dir, f)
                   for f in sorted(os.listdir(trace_dir))
                   if f.endswith("_trace.json")]
                  if os.path.isdir(trace_dir) else [])
        check(bool(traces), "tree run wrote a Chrome-trace JSON")
        if traces:
            spans = telemetry.trace_spans(telemetry.load_trace(traces[0]))
            folds = [s for s in spans if s.name == "aggregator.fold"]
            fold_aggs = {s.process for s in folds}
            check(fold_aggs >= {"aggregator-0", "aggregator-1"},
                  f"both aggregator slice folds in the trace "
                  f"(got {sorted(fold_aggs)})")
            trace_ids = {s.trace_id for s in folds}
            stitched = False
            for tid in trace_ids:
                tier = [s for s in spans if s.trace_id == tid]
                ids = {s.span_id for s in tier}
                t_folds = [s for s in tier if s.name == "aggregator.fold"
                           and s.parent_id in ids]
                t_train = [s for s in tier if s.name == "worker.train"
                           and s.parent_id in {f.span_id for f in t_folds}]
                t_coord = [s for s in tier
                           if s.process.startswith("coordinator")]
                if len(t_folds) >= 2 and t_train and t_coord:
                    stitched = True
                    break
            check(stitched,
                  "one round trace stitches coordinator -> 2 aggregator "
                  "folds -> worker train spans with parent links")

        devices = telemetry.load_health(health_dir)
        check(bool(devices),
              f"health ledger non-empty ({len(devices)} device(s))")
        check(any(h.lat_samples for h in devices.values()),
              "health ledger attributes per-device round latency")
        sources = (sorted(os.listdir(health_dir))
                   if os.path.isdir(health_dir) else [])
        check(any(s.startswith("health_aggregator") for s in sources),
              f"aggregator tier fed the ledger (files: {sources})")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()


def run_async_phase(check, env: dict) -> None:
    """Buffered-async federation: labeled staleness exposition + the
    observatory's stitched dispatch -> train -> fold lineage traces."""
    n_workers = 3
    workdir = tempfile.mkdtemp(prefix="colearn_obs_async_")
    trace_dir = os.path.join(workdir, "trace")
    health_dir = os.path.join(workdir, "health")
    cfg = _config_flags(n_workers) + ["--health-dir", health_dir]
    procs: list[subprocess.Popen] = []

    def spawn(args: list[str], **kw) -> subprocess.Popen:
        p = subprocess.Popen([sys.executable, "-m", _CLI, *args],
                             env=env, **kw)
        procs.append(p)
        return p

    try:
        broker = spawn(["broker"], stdout=subprocess.PIPE, text=True)
        addr = json.loads(broker.stdout.readline())
        host, port = addr["host"], str(addr["port"])
        for i in range(n_workers):
            log = open(os.path.join(workdir, f"worker{i}.log"), "ab")
            spawn(["worker", *cfg, "--client-id", str(i),
                   "--broker-host", host, "--broker-port", port],
                  stdout=log, stderr=log)
        coord = spawn(
            ["coordinate", *cfg, "--async-buffer", "2", "--async-observe",
             "--trace-dir", trace_dir, "--metrics-port", "0",
             "--broker-host", host, "--broker-port", port,
             "--min-devices", str(n_workers), "--round-timeout", "30",
             "--enroll-timeout", "90", "--no-evaluator"],
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)

        metrics_port = None
        scraped = False
        observed_rec = False
        for line in coord.stderr:
            try:
                doc = json.loads(line.strip())
            except json.JSONDecodeError:
                continue
            if doc.get("event") == "metrics_port":
                metrics_port = int(doc["port"])
            if "aggregation" in doc and "arrival_rate_per_s" in doc:
                observed_rec = True
            if "aggregation" in doc and not scraped and metrics_port:
                scraped = True
                url = f"http://127.0.0.1:{metrics_port}/metrics"
                text = urllib.request.urlopen(url, timeout=10) \
                    .read().decode("utf-8")
                lines = [ln for ln in text.splitlines() if ln]
                bad = [ln for ln in lines if not _PROM_LINE.match(ln)]
                check(not bad,
                      f"async scrape matches the exposition grammar "
                      f"(bad: {bad[:3]})")
                stale = [ln for ln in lines
                         if ln.startswith("colearn_async_staleness{")
                         and "outcome=" in ln]
                check(bool(stale),
                      "scrape carries the labeled staleness histogram "
                      "(async_staleness{outcome=...})")
                arrival = [ln for ln in lines if ln.startswith(
                    "colearn_async_arrival_rate_per_s")]
                check(bool(arrival),
                      "scrape carries the arrival-rate gauge")
        rc = coord.wait(timeout=180)
        check(rc == 0, f"async coordinator exited 0 (got {rc})")
        check(observed_rec,
              "observatory keys (arrival_rate_per_s) in aggregation "
              "records under --async-observe")

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from colearn_federated_learning_tpu import telemetry

        traces = ([os.path.join(trace_dir, f)
                   for f in sorted(os.listdir(trace_dir))
                   if f.endswith("_trace.json")]
                  if os.path.isdir(trace_dir) else [])
        check(bool(traces), "async run wrote a Chrome-trace JSON")
        if traces:
            spans = telemetry.trace_spans(telemetry.load_trace(traces[0]))
            folds = [s for s in spans if s.name == "fold_update"]
            check(bool(folds), "trace carries fold_update lineage spans")
            check(all("tau" in s.attrs for s in folds),
                  "every fold_update span carries tau in its args")
            check(folds and all(s.parent_id for s in folds),
                  "every fold_update span is parented on its dispatch "
                  "context")
            # Full lineage: one trace id holds dispatch -> worker train
            # -> fold for the same update.
            stitched = False
            for f in folds:
                tier = [s for s in spans if s.trace_id == f.trace_id]
                names = {s.name for s in tier}
                if {"dispatch_train", "worker.train",
                        "fold_update"} <= names:
                    stitched = True
                    break
            check(stitched,
                  "one trace stitches dispatch_train -> worker.train -> "
                  "fold_update for an update")
            aggs = [s for s in spans if s.name == "async.aggregate"]
            check(bool(aggs) and any(s.attrs.get("link_folds")
                                     for s in aggs),
                  "async.aggregate spans cross-link their folds")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()


def run_learning_phase(check, env: dict) -> None:
    """Convergence observatory over a REAL federation (--learn-observe):
    the mid-run scrape carries the learn_* instruments and the committed
    event stream carries the conv_* trail, one signal per round."""
    workdir = tempfile.mkdtemp(prefix="colearn_obs_learn_")
    events_path = os.path.join(workdir, "events.jsonl")
    cfg = _config_flags()
    procs: list[subprocess.Popen] = []

    def spawn(args: list[str], **kw) -> subprocess.Popen:
        p = subprocess.Popen([sys.executable, "-m", _CLI, *args],
                             env=env, **kw)
        procs.append(p)
        return p

    try:
        broker = spawn(["broker"], stdout=subprocess.PIPE, text=True)
        addr = json.loads(broker.stdout.readline())
        host, port = addr["host"], str(addr["port"])
        for i in range(N_WORKERS):
            log = open(os.path.join(workdir, f"worker{i}.log"), "ab")
            spawn(["worker", *cfg, "--client-id", str(i),
                   "--broker-host", host, "--broker-port", port],
                  stdout=log, stderr=log)
        coord = spawn(
            ["coordinate", *cfg, "--learn-observe",
             "--metrics-port", "0", "--events-file", events_path,
             "--broker-host", host, "--broker-port", port,
             "--min-devices", str(N_WORKERS), "--round-timeout", "30",
             "--enroll-timeout", "90", "--no-evaluator"],
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)

        metrics_port = None
        scraped = False
        for line in coord.stderr:
            try:
                doc = json.loads(line.strip())
            except json.JSONDecodeError:
                continue
            if doc.get("event") == "metrics_port":
                metrics_port = int(doc["port"])
            if "round" in doc and not scraped and metrics_port:
                scraped = True
                url = f"http://127.0.0.1:{metrics_port}/metrics"
                text = urllib.request.urlopen(url, timeout=10) \
                    .read().decode("utf-8")
                lines = [ln for ln in text.splitlines() if ln]
                bad = [ln for ln in lines if not _PROM_LINE.match(ln)]
                check(not bad,
                      f"learning scrape matches the exposition grammar "
                      f"(bad: {bad[:3]})")
                norm = [ln for ln in lines
                        if ln.startswith("colearn_learn_update_norm ")]
                check(bool(norm),
                      "scrape carries the learn_update_norm gauge")
                trend = [ln for ln in lines
                         if ln.startswith("colearn_learn_trend_total{")
                         and "trend=" in ln]
                check(bool(trend),
                      "scrape carries the labeled trend census "
                      "(learn_trend_total{trend=...})")
        rc = coord.wait(timeout=180)
        check(rc == 0, f"learning coordinator exited 0 (got {rc})")

        with open(events_path) as f:
            events = [json.loads(ln) for ln in f if ln.strip()]
        rounds = [e for e in events if e.get("event") == "round"]
        check(len(rounds) >= ROUNDS,
              f"event stream carries one event per round "
              f"({len(rounds)}/{ROUNDS})")
        trail = [e.get("conv_update_norm") for e in rounds]
        check(all(isinstance(v, (int, float)) and v > 0 for v in trail),
              f"every round event carries a conv_update_norm signal "
              f"(trail: {trail})")
        check(all("conv_trend" in e for e in rounds),
              "every round event carries a conv_trend classification")
        check(all("conv_cos_prev" in e for e in rounds[1:])
              and "conv_cos_prev" not in rounds[0],
              "conv_cos_prev absent on the first round, present after")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()


def run_classic_phase(check, env: dict) -> None:
    """Flight recorder + exporter + event stream + SIGKILL dump +
    top/postmortem over one real federation (the original smoke)."""
    workdir = tempfile.mkdtemp(prefix="colearn_obs_")
    flight_dir = os.path.join(workdir, "flight")
    events_path = os.path.join(workdir, "events.jsonl")
    cfg = _config_flags()
    obs = ["--flight-dir", flight_dir, "--flight-heartbeat", "0.5"]
    procs: list[subprocess.Popen] = []

    def spawn(args: list[str], **kw) -> subprocess.Popen:
        p = subprocess.Popen([sys.executable, "-m", _CLI, *args],
                             env=env, **kw)
        procs.append(p)
        return p

    try:
        broker = spawn(["broker"], stdout=subprocess.PIPE, text=True)
        addr = json.loads(broker.stdout.readline())
        host, port = addr["host"], str(addr["port"])
        for i in range(N_WORKERS):
            log = open(os.path.join(workdir, f"worker{i}.log"), "ab")
            spawn(["worker", *cfg, *obs, "--client-id", str(i),
                   "--broker-host", host, "--broker-port", port],
                  stdout=log, stderr=log)
        workers = procs[1:]
        coord = spawn(
            ["coordinate", *cfg, *obs,
             "--metrics-port", "0", "--events-file", events_path,
             "--broker-host", host, "--broker-port", port,
             "--min-devices", str(N_WORKERS), "--round-timeout", "25",
             "--enroll-timeout", "90", "--no-evaluator", "--elastic"],
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)

        metrics_port = None
        victim_pid = None
        scraped = False
        snapshot_body = b""
        for line in coord.stderr:
            try:
                doc = json.loads(line.strip())
            except json.JSONDecodeError:
                continue
            if doc.get("event") == "metrics_port":
                metrics_port = int(doc["port"])
            if "round" in doc and not scraped:
                scraped = True
                check(metrics_port is not None,
                      "metrics_port announced before the first round")
                if metrics_port:
                    url = f"http://127.0.0.1:{metrics_port}/metrics"
                    text = urllib.request.urlopen(url, timeout=10) \
                        .read().decode("utf-8")
                    lines = [ln for ln in text.splitlines() if ln]
                    bad = [ln for ln in lines
                           if not _PROM_LINE.match(ln)]
                    check(not bad,
                          f"every /metrics line matches the exposition "
                          f"grammar (bad: {bad[:3]})")
                    check(any(ln.startswith("colearn_") for ln in lines),
                          "scrape carries colearn_* samples")
                    snapshot_body = urllib.request.urlopen(
                        f"http://127.0.0.1:{metrics_port}/snapshot.json",
                        timeout=10).read()
                    check(bool(json.loads(snapshot_body)),
                          "/snapshot.json serves the live registry")
                # Induced kill: the dump the recorder's heartbeat left
                # behind must survive an uncatchable SIGKILL.
                victim = workers[-1]
                victim_pid = victim.pid
                time.sleep(1.0)          # > one 0.5 s heartbeat period
                victim.send_signal(signal.SIGKILL)
        rc = coord.wait(timeout=120)
        check(rc == 0, f"coordinator exited 0 (got {rc})")

        # Replay the mid-run snapshot for `colearn top --once` so the
        # render path is exercised on real federation data without
        # racing the (long-gone) coordinator's exporter.
        if snapshot_body:
            import threading
            from http.server import (BaseHTTPRequestHandler,
                                     ThreadingHTTPServer)

            class _Replay(BaseHTTPRequestHandler):
                def do_GET(self):      # noqa: N802 (stdlib handler name)
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length",
                                     str(len(snapshot_body)))
                    self.end_headers()
                    self.wfile.write(snapshot_body)

                def log_message(self, fmt, *log_args):
                    pass

            srv = ThreadingHTTPServer(("127.0.0.1", 0), _Replay)
            threading.Thread(target=srv.serve_forever,
                             daemon=True).start()
            try:
                top = subprocess.run(
                    [sys.executable, "-m", _CLI, "top", "--once",
                     "--url", f"http://127.0.0.1:"
                     f"{srv.server_address[1]}/snapshot.json"],
                    env=env, capture_output=True, text=True, timeout=60)
            finally:
                srv.shutdown()
                srv.server_close()
            check(top.returncode == 0 and bool(top.stdout.strip()),
                  f"colearn top --once renders the captured snapshot"
                  f" (rc={top.returncode},"
                  f" err={top.stderr.strip()[:200]!r})")
        else:
            check(False, "no /snapshot.json captured mid-run")

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from colearn_federated_learning_tpu.telemetry import flight

        dumps = flight.load_flight_dumps(flight_dir)
        dumped = {d.get("pid") for d in dumps if "error" not in d}
        check(victim_pid in dumped,
              f"SIGKILLed worker pid {victim_pid} left a parseable "
              f"flight dump (found pids: {sorted(dumped)})")

        with open(events_path) as f:
            events = [json.loads(ln) for ln in f if ln.strip()]
        check(any(e.get("event") == "start" for e in events),
              "event stream carries the start event")
        n_round_events = sum(1 for e in events if e.get("event") == "round")
        check(n_round_events >= ROUNDS,
              f"event stream carries one event per round "
              f"({n_round_events}/{ROUNDS})")

        pm = subprocess.run(
            [sys.executable, "-m", _CLI, "postmortem", flight_dir,
             "--format", "json"],
            env=env, capture_output=True, text=True, timeout=60)
        ok_pm = pm.returncode == 0
        if ok_pm:
            report = json.loads(pm.stdout)
            ok_pm = len(report.get("processes", [])) >= 1
        check(ok_pm, "colearn postmortem parses the flight dir")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            p.wait()

_PHASES = {
    "classic": run_classic_phase,
    "tree": run_tree_phase,
    "async": run_async_phase,
    "learning": run_learning_phase,
}


def main(argv=None) -> int:
    names = list(argv if argv is not None else sys.argv[1:])
    unknown = [n for n in names if n not in _PHASES]
    if unknown:
        print(f"[obs-smoke] unknown phase(s) {unknown}; "
              f"choose from {sorted(_PHASES)}", file=sys.stderr)
        return 2
    if not names:
        names = ["classic", "tree", "async", "learning"]
    # A CPU tool: its many processes must not contend for one chip.
    env = dict(os.environ, PYTHONUNBUFFERED="1", JAX_PLATFORMS="cpu")
    failures: list[str] = []

    def check(ok: bool, label: str) -> None:
        print(f"[obs-smoke] {'ok' if ok else 'FAIL'}: {label}",
              file=sys.stderr)
        if not ok:
            failures.append(label)

    for name in names:
        print(f"[obs-smoke] phase: {name}", file=sys.stderr)
        _PHASES[name](check, env)

    if failures:
        print(f"[obs-smoke] {len(failures)} check(s) failed",
              file=sys.stderr)
        return 1
    print("[obs-smoke] all checks passed", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
