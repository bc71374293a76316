"""`colearn` command line: train / aggregate / eval / init / configs.

Parity surface (BASELINE.json north_star): the reference exposes
``colearn train`` and ``colearn aggregate`` entrypoints and argparse flags
for rounds/epochs/lr/client count (SURVEY.md §2 "Config/scripts"); both
accept ``--backend=tpu|cpu|auto`` here.

Two federation modes:
- ``train`` (default role ``sim``): the TPU-native simulation — every client
  trains on-device in one jit program (fed/engine.py).
- ``train --role client`` + ``aggregate``: cross-silo over files — each silo
  produces an update file against a global-model file; the aggregator folds
  them with the configured server strategy (fed/offline.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

from colearn_federated_learning_tpu.utils.compile_cache import (
    enable_compile_cache,
)
from colearn_federated_learning_tpu.utils.config import (
    CONFIGS,
    ExperimentConfig,
    get_config,
)


def _add_override_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", default="mnist_mlp_fedavg",
                   help=f"experiment config; one of {sorted(CONFIGS)}")
    p.add_argument("--backend", choices=["auto", "tpu", "cpu"], default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--tp-size", type=int, default=None,
                   help="model-axis size: shard the global model over a "
                        "tensor-parallel mesh (engine) and the server "
                        "plane over a (model,) mesh (coordinator; "
                        "parallel/partition.py)")
    p.add_argument("--rounds", type=int, default=None)
    p.add_argument("--num-clients", type=int, default=None)
    p.add_argument("--cohort-size", type=int, default=None)
    p.add_argument("--local-epochs", type=int, default=None)
    p.add_argument("--local-steps", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--lr-schedule", default=None,
                   choices=["constant", "cosine", "warmup_cosine"],
                   help="client-lr schedule across rounds "
                        "(fed/strategies.lr_scale_for_round)")
    p.add_argument("--warmup-rounds", type=int, default=None)
    p.add_argument("--lr-min-fraction", type=float, default=None,
                   help="cosine floor as a fraction of --lr")
    p.add_argument("--momentum", type=float, default=None)
    p.add_argument("--local-optimizer", default=None,
                   choices=["sgd", "adam", "adamw"])
    p.add_argument("--strategy", default=None,
                   choices=["fedavg", "fedprox", "fedadam", "fedyogi",
                            "scaffold", "fednova"])
    p.add_argument("--prox-mu", type=float, default=None)
    p.add_argument("--aggregator", default=None,
                   choices=["mean", "median", "trimmed_mean", "krum"],
                   help="Byzantine-robust server aggregation (fed/robust.py)")
    p.add_argument("--trim-fraction", type=float, default=None)
    p.add_argument("--edge-groups", type=int, default=None,
                   help=">= 2 turns on hierarchical edge->cloud federation "
                        "(fed/hierarchical.py)")
    p.add_argument("--edge-sync-period", type=int, default=None)
    p.add_argument("--dataset", default=None)
    p.add_argument("--partition", default=None,
                   choices=["iid", "dirichlet", "pathological"])
    p.add_argument("--dirichlet-alpha", type=float, default=None)
    p.add_argument("--dp-clip", type=float, default=None)
    p.add_argument("--dp-noise-multiplier", type=float, default=None)
    p.add_argument("--dp-delta", type=float, default=None,
                   help="δ at which the RDP accountant reports ε")
    p.add_argument("--dp-adaptive-clip", action="store_true", default=None,
                   help="track the --dp-target-quantile of update norms "
                        "(--dp-clip becomes the initial norm)")
    p.add_argument("--dp-target-quantile", type=float, default=None)
    p.add_argument("--dp-clip-lr", type=float, default=None)
    p.add_argument("--dp-bit-noise", type=float, default=None,
                   help="σ_b on the quantile-bit sum (0 = cohort/20)")
    p.add_argument("--secure-agg", action="store_true", default=None)
    p.add_argument("--secure-agg-neighbors", type=int, default=None,
                   help="k-regular random-ring masking (0 = all pairs)")
    p.add_argument("--compress", default=None,
                   choices=["none", "int8", "topk", "topk8"],
                   help="update compression on the wire/file planes "
                        "(topk8: int8 values inside the topk frame)")
    p.add_argument("--compress-feedback", action="store_true", default=None,
                   help="carry the uplink compression residual into the "
                        "next round's delta (error feedback; rejected "
                        "under secure_agg)")
    p.add_argument("--topk-fraction", type=float, default=None,
                   help="topk keep density (fraction of entries per leaf)")
    p.add_argument("--topk-adaptive", action="store_true", default=None,
                   help="steer each worker's topk density off its "
                        "error-feedback residual norm, clipped to "
                        "[--topk-min-fraction, --topk-max-fraction] "
                        "(needs --compress topk + feedback)")
    p.add_argument("--topk-min-fraction", type=float, default=None)
    p.add_argument("--topk-max-fraction", type=float, default=None)
    p.add_argument("--lora-rank", type=int, default=None,
                   help="rank-r LoRA adapter federation (fed/lora.py): "
                        "clients train and ship rank-r factors instead "
                        "of dense deltas (0 = off)")
    p.add_argument("--lora-alpha", type=float, default=None,
                   help="LoRA scaling numerator: merged delta is "
                        "B·A·(alpha/rank)")
    p.add_argument("--lora-merge-every", type=int, default=None,
                   help="server merges aggregated factors into the "
                        "global model every N aggregations")
    p.add_argument("--num-aggregators", type=int, default=None,
                   help="aggregator-tree fan-in: N `colearn aggregator` "
                        "processes each fold one cohort slice and ship "
                        "one partial sum to the coordinator "
                        "(comm/aggregator.py; 0 = flat)")
    p.add_argument("--agg-heartbeat-timeout", type=float, default=None,
                   help="treat an aggregator as dead when its retained "
                        "heartbeat is older than this many seconds")
    p.add_argument("--agg-buffer-interval", type=float, default=None,
                   dest="agg_buffer_interval_s",
                   help="tree-async fold cadence: each aggregator's "
                        "per-slice buffer targets one partial ship per "
                        "this many seconds (buffer depth auto-sizes from "
                        "the slice's measured arrival rate)")
    p.add_argument("--fold-device", action="store_true", default=None,
                   help="device-resident fold (ops/fold_kernel.py): "
                        "server folds run through the fused batched "
                        "kernel — in-kernel topk8 dequant + weighting + "
                        "scatter-add, one compile per model — instead "
                        "of the per-update host-numpy scatter; bitwise "
                        "identical to the host fold")
    p.add_argument("--compress-down", default=None,
                   choices=["none", "int8", "topk"],
                   help="DOWNLINK broadcast compression (synchronous "
                        "coordinator): ship the server delta against a "
                        "worker-side param cache (comm/downlink.py)")
    p.add_argument("--straggler-prob", type=float, default=None)
    p.add_argument("--eval-every", type=int, default=None)
    p.add_argument("--log-every", type=int, default=None)
    p.add_argument("--log-file", default=None)
    p.add_argument("--tensorboard-dir", default=None,
                   help="mirror scalar round metrics to TensorBoard")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=None)
    p.add_argument("--ckpt-stream", action="store_true", default=None,
                   help="shard-native streaming checkpoints "
                        "(ckpt/streaming.py): per-shard CRC-checked "
                        "files + a manifest commit marker fsynced last; "
                        "--resume re-shards onto the current mesh "
                        "without assembling the full tree")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of rounds 1-2 here")
    p.add_argument("--trace-dir", default=None,
                   help="write a Chrome-trace JSON of per-round phase spans "
                        "here (open in Perfetto / chrome://tracing, or use "
                        "`colearn trace-summary`)")
    p.add_argument("--trace-rounds", type=int, default=None,
                   help="span-trace only the first N rounds (0 = all)")
    p.add_argument("--attn-impl", default=None,
                   choices=["dense", "flash", "ring", "ulysses"],
                   help="attention core (models/attention.py)")
    p.add_argument("--width", type=int, default=None,
                   help="model width override (CNN channels / embed dim)")
    p.add_argument("--stem", default=None,
                   choices=["conv", "space_to_depth"],
                   help="CNN stem MFU lever (models/cnn.py)")
    p.add_argument("--norm", default=None, choices=["group", "none"],
                   help="CNN normalization (group | none)")
    p.add_argument("--remat", action="store_true", default=None,
                   help="rematerialize transformer blocks (jax.checkpoint): "
                        "activation HBM ~depth -> ~1 block")
    p.add_argument("--min-cohort-fraction", type=float, default=None,
                   help="aggregation quorum: a round whose completed "
                        "fraction of the cohort falls below this is an "
                        "explicit no-op (0 disables)")
    p.add_argument("--evict-after", type=int, default=None,
                   help="evict a device after N consecutive failed rounds "
                        "(>= 1)")
    p.add_argument("--comm-retries", type=int, default=None,
                   help="transport retries per request on transient "
                        "failures, budgeted against the round deadline "
                        "(0 disables)")
    p.add_argument("--comm-backoff-base", type=float, default=None,
                   help="retry backoff base seconds (exponential + full "
                        "jitter)")
    p.add_argument("--comm-backoff-max", type=float, default=None,
                   help="retry backoff cap seconds")
    p.add_argument("--worker-enroll-timeout", type=float, default=None,
                   help="worker-side role-assignment window in seconds; "
                        "expiry raises EnrollmentTimeout instead of "
                        "hanging")
    p.add_argument("--health-dir", default=None,
                   help="per-device health ledger directory "
                        "(telemetry/health.py): coordinator/aggregator/"
                        "fleetsim durably record deadline misses, "
                        "retries, latency sketches per device "
                        "(`colearn health` reads it)")
    p.add_argument("--learn-observe", action="store_true", default=None,
                   help="convergence observatory "
                        "(telemetry/convergence.py): stamp conv_* "
                        "learning-health keys (update norm, cosine to "
                        "the previous update, EWMA trend) on round "
                        "records and export learn.* metrics; `colearn "
                        "converge` renders the report")
    p.add_argument("--fault-plan", default=None,
                   help="JSON fault-plan file (faults/plan.py) installed "
                        "on this process's transport — deterministic "
                        "chaos testing")
    p.add_argument("--fault-seed", type=int, default=None,
                   help="override the fault plan's seed")


def _add_observability_flags(p: argparse.ArgumentParser) -> None:
    """Opt-in runtime observability plane for long-running processes
    (worker/coordinate): crash flight recorder, Prometheus endpoint,
    JSONL event stream.  All off by default — zero threads, zero files."""
    p.add_argument("--flight-dir", default=None,
                   help="crash flight recorder: heartbeat-rewrite a "
                        "bounded black box (flight_<pid>.json) here; "
                        "survives SIGKILL up to one heartbeat of "
                        "staleness (`colearn postmortem` reads these)")
    p.add_argument("--flight-heartbeat", type=float, default=5.0,
                   help="flight-recorder rewrite period in seconds")
    p.add_argument("--flight-watchdog", type=float, default=None,
                   help="declare a stall (and dump) after this many "
                        "seconds without round progress")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve /metrics (Prometheus text) and "
                        "/snapshot.json on 127.0.0.1:<port>; 0 binds an "
                        "ephemeral port announced as a metrics_port "
                        "event on stderr")
    p.add_argument("--events-file", default=None,
                   help="append lifecycle + round events as JSONL here "
                        "(push half of the export plane)")


def _setup_observability(args: argparse.Namespace, role: str,
                         tracers: tuple = ()) -> tuple:
    """Install whichever observability features the flags opted into.
    Returns ``(exporter, events, recorder)`` — each None when off."""
    from colearn_federated_learning_tpu import telemetry

    recorder = exporter = events = None
    if args.flight_dir:
        recorder = telemetry.install_flight_recorder(
            args.flight_dir, role=role,
            heartbeat_s=args.flight_heartbeat,
            watchdog_s=args.flight_watchdog)
        for tr in tracers:
            recorder.attach_tracer(tr)
    if args.metrics_port is not None:
        exporter = telemetry.MetricsExporter(port=args.metrics_port).start()
        print(json.dumps({"event": "metrics_port", "port": exporter.port}),
              file=sys.stderr)
    if args.events_file:
        events = telemetry.EventLog(args.events_file)
        events.emit("start", role=role)
    return exporter, events, recorder


def _obs_round_hook(events, recorder):
    """Per-round-record side channel: event-stream line + flight-ring
    entry + watchdog progress mark.  Cheap no-op when both are off."""
    def hook(rec: dict) -> None:
        if events is not None:
            events.emit("round", **{
                k: v for k, v in rec.items()
                if isinstance(v, (int, float, str, bool))})
        if recorder is not None:
            recorder.record("round", round=rec.get("round"))
            recorder.mark_progress()
    return hook


_FED_KEYS = {"rounds", "cohort_size", "local_epochs", "local_steps",
             "batch_size", "lr", "lr_schedule", "warmup_rounds",
             "lr_min_fraction", "momentum", "local_optimizer", "strategy",
             "prox_mu", "dp_clip", "dp_noise_multiplier", "dp_delta",
             "dp_adaptive_clip", "dp_target_quantile", "dp_clip_lr",
             "dp_bit_noise", "secure_agg", "secure_agg_neighbors",
             "straggler_prob", "compress", "compress_down", "aggregator",
             "compress_feedback", "topk_fraction", "topk_adaptive",
             "topk_min_fraction", "topk_max_fraction",
             "lora_rank", "lora_alpha", "lora_merge_every",
             "trim_fraction", "edge_groups", "edge_sync_period",
             "min_cohort_fraction"}
_DATA_KEYS = {"num_clients", "dataset", "partition", "dirichlet_alpha"}
_MODEL_KEYS = {"attn_impl", "remat", "stem", "norm", "width"}
_RUN_KEYS = {"backend", "seed", "tp_size", "eval_every", "log_every",
             "checkpoint_dir",
             "checkpoint_every", "profile_dir", "trace_dir", "trace_rounds",
             "evict_after", "worker_enroll_timeout", "comm_retries",
             "comm_backoff_base", "comm_backoff_max", "fault_plan",
             "fault_seed", "num_aggregators", "agg_heartbeat_timeout",
             "agg_buffer_interval_s", "health_dir", "learn_observe",
             "fold_device", "ckpt_stream"}


def config_from_args(args: argparse.Namespace) -> ExperimentConfig:
    """Resolve the experiment config and — BEFORE jax initializes a
    backend, after which the platform is fixed — honor ``--backend=cpu``
    so that a CPU run on a machine with a chip does not take the chip."""
    if getattr(args, "backend", None) == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    cfg = get_config(args.config)
    sections = {"fed": {}, "data": {}, "model": {}, "run": {}}
    for key, val in vars(args).items():
        if val is None:
            continue
        if key in _FED_KEYS:
            sections["fed"][key] = val
        elif key in _DATA_KEYS:
            sections["data"][key] = val
        elif key in _MODEL_KEYS:
            sections["model"][key] = val
        elif key in _RUN_KEYS:
            sections["run"][key] = val
    return cfg.replace(
        fed=dataclasses.replace(cfg.fed, **sections["fed"]),
        data=dataclasses.replace(cfg.data, **sections["data"]),
        model=dataclasses.replace(cfg.model, **sections["model"]),
        run=dataclasses.replace(cfg.run, **sections["run"]),
    )


def cmd_train(args: argparse.Namespace) -> int:
    config = config_from_args(args)

    if args.role == "client":
        from colearn_federated_learning_tpu.fed import offline

        if args.client_id is None or not args.global_model or not args.out:
            print("train --role client requires --client-id, --global-model, "
                  "--out", file=sys.stderr)
            return 2
        stats = offline.client_update(config, args.client_id,
                                      args.global_model, args.out,
                                      residual_path=args.residual_path)
        print(json.dumps(stats))
        return 0

    from colearn_federated_learning_tpu.fed.engine import FederatedLearner
    from colearn_federated_learning_tpu.metrics import MetricsLogger

    if config.fed.edge_groups >= 2:
        from colearn_federated_learning_tpu.fed.hierarchical import (
            HierarchicalLearner,
        )

        unsupported = [
            flag for flag, on in [
                ("--resume", args.resume),
                ("--per-client-eval", args.per_client_eval),
                ("--detection-eval", args.detection_eval),
                ("--personalize-steps", bool(args.personalize_steps)),
                ("--checkpoint-dir", bool(config.run.checkpoint_dir)),
                ("--profile-dir", bool(config.run.profile_dir)),
                ("--trace-dir", bool(config.run.trace_dir)),
            ] if on
        ]
        if unsupported:
            print(f"--edge-groups does not support {', '.join(unsupported)}",
                  file=sys.stderr)
            return 2
        learner = HierarchicalLearner(
            config, num_groups=config.fed.edge_groups,
            sync_period=config.fed.edge_sync_period,
        )
        with MetricsLogger(path=args.log_file, name=config.run.name,
                           tensorboard_dir=args.tensorboard_dir) as logger:
            learner.fit(log_fn=lambda rec: (
                logger.log(rec), print(json.dumps(rec), file=sys.stderr)
            ))
            loss, acc = learner.evaluate()
            print(json.dumps({"name": config.run.name,
                              "rounds": len(learner.history),
                              "edge_groups": config.fed.edge_groups,
                              "final_loss": loss, "final_acc": acc,
                              "data_source": learner.dataset.source}))
        return 0

    learner = FederatedLearner.from_config(config)
    with MetricsLogger(path=args.log_file, name=config.run.name,
                       tensorboard_dir=args.tensorboard_dir) as logger:
        if args.resume:
            step = learner.restore_checkpoint()
            print(f"resumed at round {step}", file=sys.stderr)

        def log_fn(rec):
            logger.log(rec)
            print(json.dumps(rec), file=sys.stderr)

        learner.fit(log_fn=log_fn)
        def dump_report(rep):
            from colearn_federated_learning_tpu.fed.evaluation import (
                sanitize_report,
            )

            print(json.dumps(sanitize_report(rep)), file=sys.stderr)

        if args.per_client_eval:
            dump_report(learner.evaluate_per_client())
        if args.personalize_steps:
            dump_report(
                learner.evaluate_personalized(steps=args.personalize_steps))
        if args.detection_eval:
            dump_report(learner.evaluate_detection())
        samples = (learner.cohort_size * learner.num_steps
                   * config.fed.batch_size)
        devices = learner.devices
        summary = logger.summary(samples_per_round=samples,
                                 n_chips=len(devices))
        # Every result names the device it ran on.
        summary.update(platform=devices[0].platform,
                       device_kind=devices[0].device_kind,
                       n_chips=len(devices))
        # Which registry branch fed the run — so a user who staged real
        # data under $COLEARN_DATA_DIR can confirm it was actually used.
        summary["data_source"] = learner.dataset.source
        if learner.last_trace_path:
            summary["trace_file"] = learner.last_trace_path
        print(json.dumps(summary))
    return 0


def cmd_init(args: argparse.Namespace) -> int:
    from colearn_federated_learning_tpu.fed import offline

    config = config_from_args(args)
    offline.init_global_model(config, args.out)
    print(json.dumps({"out": args.out, "round": 0}))
    return 0


def cmd_aggregate(args: argparse.Namespace) -> int:
    from colearn_federated_learning_tpu.fed import offline

    config = config_from_args(args)
    stats = offline.aggregate_updates(config, args.global_model, args.updates,
                                      args.out)
    print(json.dumps(stats))
    return 0


def cmd_eval(args: argparse.Namespace) -> int:
    from colearn_federated_learning_tpu.fed import offline

    config = config_from_args(args)
    print(json.dumps(offline.evaluate_global(
        config, args.global_model, detection=args.detection_eval)))
    return 0


def cmd_broker(args: argparse.Namespace) -> int:
    import threading

    from colearn_federated_learning_tpu.comm.broker import MessageBroker

    exporter, events, recorder = _setup_observability(args, role="broker")
    broker = MessageBroker(host=args.host, port=args.port).start()
    print(json.dumps({"host": broker.host, "port": broker.port}), flush=True)
    if recorder is not None:
        recorder.record("broker_listening", port=broker.port)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        broker.stop()
        if events is not None:
            events.emit("stop", role="broker")
        if exporter is not None:
            exporter.close()
    return 0


def _install_fault_plan(config: ExperimentConfig) -> None:
    """Install ``--fault-plan`` on this process's transport (chaos
    testing).  A no-op without the flag — the transport then pays a
    single pointer check per message."""
    if not config.run.fault_plan:
        return
    from colearn_federated_learning_tpu import faults

    plan = faults.FaultPlan.load(config.run.fault_plan,
                                 seed=config.run.fault_seed or None)
    faults.install(plan)
    print(f"fault plan installed: {len(plan.faults)} spec(s), "
          f"seed {plan.seed}", file=sys.stderr)


def cmd_worker(args: argparse.Namespace) -> int:
    from colearn_federated_learning_tpu.comm.worker import run_worker_forever

    config = config_from_args(args)
    if args.client_id is None:
        print("worker requires --client-id", file=sys.stderr)
        return 2
    _install_fault_plan(config)
    _setup_observability(args, role=f"worker{args.client_id}")
    mud = None
    if args.mud_profile:
        with open(args.mud_profile) as f:
            mud = f.read()
    run_worker_forever(config, args.client_id, args.broker_host,
                       args.broker_port, mud_profile=mud)
    return 0


def cmd_aggregator(args: argparse.Namespace) -> int:
    from colearn_federated_learning_tpu.comm.aggregator import (
        run_aggregator_forever,
    )

    config = config_from_args(args)
    if args.agg_id is None:
        print("aggregator requires --agg-id", file=sys.stderr)
        return 2
    _install_fault_plan(config)
    _setup_observability(args, role=f"aggregator{args.agg_id}")
    run_aggregator_forever(config, args.agg_id, args.broker_host,
                           args.broker_port, heartbeat_s=args.heartbeat)
    return 0


def _write_coordinator_trace(config, coord) -> None:
    """Flush the coordinator's span buffer (round phases + adopted worker
    spans) to a Chrome-trace JSON when --trace-dir is set."""
    if not config.run.trace_dir:
        return
    from colearn_federated_learning_tpu import telemetry

    path = telemetry.write_tracer(
        config.run.trace_dir, config.run.name, coord.tracer,
        metrics=telemetry.get_registry().snapshot(),
    )
    print(f"trace written to {path}", file=sys.stderr)


def _coordinator_resume(coord) -> None:
    """Tolerant ``--resume``: restore the latest checkpoint if one exists,
    else start cold (a coordinator killed before its FIRST checkpoint has
    nothing to restore — that must not crash the recovery supervisor).
    Emits a machine-readable event line either way; the mp chaos harness
    (faults/procsoak.py) keys its resume ledger on it."""
    from colearn_federated_learning_tpu import telemetry

    try:
        step = coord.restore_checkpoint()
    except FileNotFoundError:
        print(json.dumps({"event": "resume_cold"}), file=sys.stderr)
        return
    reg = telemetry.get_registry()
    event = {
        "event": "resumed", "round": step,
        "rounds_resumed_total": reg.counter(
            "fed.rounds_resumed_total").value,
    }
    ckpt = getattr(coord, "_ckpt", None)
    digest = getattr(ckpt, "last_restore_digest", None)
    if digest is not None:
        # Streaming restore: the digest is over the full-leaf host bytes
        # in flatten order, so it is tp-layout-independent — the chaos
        # harness compares it against load_generation_host's digest of
        # the generation it expects to survive the kill.
        event["ckpt_digest"] = digest
        event["ckpt_discarded"] = sum(
            getattr(ckpt, "generations_discarded", {}).values())
        event["resharded"] = reg.counter(
            "ckpt.resharded_resumes_total").value
    print(json.dumps(event), file=sys.stderr)


def _async_buffer_arg(value: str):
    """``--async-buffer``: 0 (off), a positive int K, or ``auto`` —
    adaptive K sized from the observed arrival rate
    (telemetry/arrival.py)."""
    if value == "auto":
        return "auto"
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"expected an integer or 'auto', got {value!r}") from None


def cmd_coordinate(args: argparse.Namespace) -> int:
    from colearn_federated_learning_tpu.comm.coordinator import (
        FederatedCoordinator,
    )

    config = config_from_args(args)
    _install_fault_plan(config)
    _exporter, events, recorder = _setup_observability(
        args, role="coordinator")
    obs = _obs_round_hook(events, recorder)
    mud_policy = None
    if args.mud_require_profile or args.mud_allowed_types:
        from colearn_federated_learning_tpu.comm.mud import MudPolicy

        mud_policy = MudPolicy(
            require_profile=args.mud_require_profile,
            allowed_types=tuple(
                t for t in (args.mud_allowed_types or "").split(",") if t
            ),
        )
    if args.per_type:
        from colearn_federated_learning_tpu.comm.per_type import (
            PerTypeFederation,
        )

        fed = PerTypeFederation(
            config, args.broker_host, args.broker_port,
            round_timeout=args.round_timeout, mud_policy=mud_policy,
            min_devices_per_type=args.min_per_type,
        )

        def log_line(t, rec):
            # One atomic write per record: federation threads log
            # concurrently and print()'s separate newline write could
            # interleave lines mid-JSON.
            sys.stderr.write(json.dumps({"type": t, **rec}) + "\n")
            obs({"type": t, **rec})

        try:
            hists = fed.run(
                min_devices=args.min_devices,
                enroll_timeout=args.enroll_timeout,
                want_evaluator=not args.no_evaluator,
                log_fn=log_line,
            )
            print(json.dumps({
                "types": {t: (h[-1] if h else None)
                          for t, h in hists.items()},
                "skipped": fed.skipped,
                "errors": fed.errors,
            }))
        finally:
            fed.close()
        return 0 if hists and not fed.errors else 1
    if args.async_buffer:
        from colearn_federated_learning_tpu.comm.async_coordinator import (
            AsyncFederatedCoordinator,
        )

        coord = AsyncFederatedCoordinator(
            config, args.broker_host, args.broker_port,
            buffer_size=args.async_buffer,
            request_timeout=args.round_timeout,
            want_evaluator=not args.no_evaluator,
            mud_policy=mud_policy,
            prune_after=args.async_prune_after,
            prune_score=args.async_prune_score,
            probation=args.async_probation,
            observe=args.async_observe,
        )
        if recorder is not None:
            recorder.attach_tracer(coord.tracer)
        with coord:
            if args.resume:
                _coordinator_resume(coord)
            coord.enroll(min_devices=args.min_devices,
                         timeout=args.enroll_timeout)
            if coord.tree_mode:
                aggs = coord.enroll_aggregators(timeout=args.enroll_timeout)
                print(json.dumps({"event": "aggregators_enrolled",
                                  "aggregators": aggs}), file=sys.stderr)
            remaining = max(0, config.fed.rounds - len(coord.history))
            hist = coord.fit(
                aggregations=remaining,
                log_fn=lambda rec: (print(json.dumps(rec), file=sys.stderr),
                                    obs(rec))[0],
                elastic=args.elastic,
            )
            _write_coordinator_trace(config, coord)
            print(json.dumps(hist[-1]))
        return 0
    coord = FederatedCoordinator(config, args.broker_host, args.broker_port,
                                 round_timeout=args.round_timeout,
                                 want_evaluator=not args.no_evaluator,
                                 mud_policy=mud_policy)
    if recorder is not None:
        recorder.attach_tracer(coord.tracer)
    with coord:
        if args.resume:
            _coordinator_resume(coord)
        coord.enroll(min_devices=args.min_devices,
                     timeout=args.enroll_timeout)
        if args.resume:
            # Challenge-on-resume: retained announcements alone readmit
            # nobody — only ledger-known devices that answer the nonce
            # challenge keep their seat (comm/coordinator.py).
            verdict = coord.verify_resumed_devices()
            print(json.dumps({"event": "challenge_verified", **verdict}),
                  file=sys.stderr)
        if coord.num_aggregators:
            aggs = coord.enroll_aggregators(timeout=args.enroll_timeout)
            print(json.dumps({"event": "aggregators_enrolled",
                              "aggregators": aggs}), file=sys.stderr)
        hist = coord.fit(log_fn=lambda rec: (print(json.dumps(rec),
                                                   file=sys.stderr),
                                             obs(rec))[0],
                         elastic=args.elastic)
        if args.per_client_eval:
            print(json.dumps(coord.evaluate_per_client()), file=sys.stderr)
        _write_coordinator_trace(config, coord)
        print(json.dumps(hist[-1]))
    return 0


def _lock_witness_ok(summary: dict, args: argparse.Namespace) -> bool:
    """Lock-witness gate for the async/tree-async soaks: with
    ``--lock-witness`` the fleet must have produced per-process reports
    showing real lock traffic and ZERO witnessed ordering inversions or
    unguarded guarded-structure accesses."""
    if not args.lock_witness:
        return True
    lw = summary.get("lock_witness") or {}
    ok = (bool(lw.get("enabled"))
          and int(lw.get("reports", 0)) >= 1
          and int(lw.get("acquires", 0)) >= 1
          and int(lw.get("inversions", 0)) == 0
          and int(lw.get("unguarded", 0)) == 0)
    if not ok:
        print(f"# lock-witness gate failed: "
              f"{json.dumps({k: lw.get(k) for k in ('enabled', 'reports', 'acquires', 'inversions', 'unguarded')})}",
              file=sys.stderr)
        for rec in (lw.get("inversion_records", [])
                    + lw.get("unguarded_records", [])):
            print(f"#   {json.dumps(rec)}", file=sys.stderr)
    return ok


def cmd_chaos(args: argparse.Namespace) -> int:
    """Chaos soak.  Default: broker + workers + coordinator in THIS
    process, a fault plan installed after the warmup round (faults/soak).
    ``--mp``: broker, coordinator and workers as real subprocesses on
    real ports, SIGKILLed on a seeded schedule — including the
    coordinator, which must come back with --resume (faults/procsoak).
    ``--secure``: DH secure-aggregation federation vs a plain-FedAvg
    oracle in lockstep, maskers dropped after-fold/before-unmask; exact
    per-round param agreement is the gate (faults/soak.run_secure_soak).
    ``--ckpt``: streaming-checkpoint crash consistency — SIGKILL lands
    mid-save, --resume restores the last committed generation bitwise
    across a tp=2 -> tp=1 re-shard (faults/procsoak.run_ckpt_soak)."""
    if args.secure and args.mp:
        print("--secure is an in-process exactness gate; drop --mp",
              file=sys.stderr)
        return 2
    if args.agg and (args.secure or args.mp):
        print("--agg is its own multi-process gate; drop --secure/--mp",
              file=sys.stderr)
        return 2
    if args.chaos_async and (args.secure or args.mp or args.agg):
        print("--async is its own multi-process gate; "
              "drop --secure/--mp/--agg", file=sys.stderr)
        return 2
    if args.chaos_tree_async and (args.secure or args.mp or args.agg
                                  or args.chaos_async):
        print("--tree-async is its own multi-process gate; "
              "drop --secure/--mp/--agg/--async", file=sys.stderr)
        return 2
    if args.ckpt and (args.secure or args.mp or args.agg
                      or args.chaos_async or args.chaos_tree_async):
        print("--ckpt is its own multi-process gate; "
              "drop --secure/--mp/--agg/--async/--tree-async",
              file=sys.stderr)
        return 2
    if args.lock_witness and not (args.chaos_async
                                  or args.chaos_tree_async):
        print("--lock-witness instruments the buffered-async fleets; "
              "pair it with --async or --tree-async", file=sys.stderr)
        return 2
    if args.ckpt:
        from colearn_federated_learning_tpu.faults import procsoak

        summary = procsoak.run_ckpt_soak(
            rounds=args.rounds, n_workers=args.num_workers,
            workdir=args.workdir, round_timeout=args.mp_round_timeout,
            timeout_s=args.mp_timeout, kill=not args.no_faults,
            log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr),
        )
        print(json.dumps(summary))
        if summary["mode"] == "smoke":
            # Kill-free bitwise smoke: a tp=2 run's final generation must
            # resume bitwise-identically on tp=1 (digest match across the
            # re-shard, no kill involved).
            ok = (summary["exit_code"] == 0
                  and summary["resume_exit_code"] == 0
                  and summary["rounds_run"] >= args.rounds
                  and summary["resume_round_ok"]
                  and summary["digest_ok"]
                  and summary["reshard_ok"])
        else:
            # SIGKILL-during-save gate: the kill landed mid-save, the
            # resume fell back to the last COMMITTED generation (at most
            # one uncommitted generation lost) and restored it bitwise
            # across the tp=2 -> tp=1 re-shard, the federation finished
            # with loss parity vs the kill-free oracle, and the
            # postmortem attributes the kill.
            ok = (summary["exit_code"] == 0
                  and summary["oracle_exit_code"] == 0
                  and summary["rounds_run"] >= args.rounds
                  and summary["killed_mid_save"]
                  and summary["resumed"] >= 1
                  and summary["resume_round_ok"]
                  and summary["digest_ok"]
                  and summary["reshard_ok"]
                  and summary["loss_gap_ok"]
                  and summary["postmortem_attributed"]
                  and not summary["flight_missing"])
        return 0 if ok else 1
    if args.chaos_tree_async:
        from colearn_federated_learning_tpu.faults import procsoak

        summary = procsoak.run_tree_async_soak(
            aggregations=args.rounds, n_workers=args.num_workers,
            workdir=args.workdir, round_timeout=args.mp_round_timeout,
            timeout_s=args.mp_timeout, kill=not args.no_faults,
            log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr),
            lock_witness=args.lock_witness,
        )
        print(json.dumps(summary))
        ok = (_lock_witness_ok(summary, args)
              and summary["exit_code"] == 0
              and summary["oracle_exit_code"] == 0
              and summary["aggregations_run"] >= args.rounds
              and summary["oracle_aggregations_run"] >= args.rounds
              and summary["version_monotonic"]
              # The tree-async invariants a dead aggregator must not
              # break: a contribution folds exactly once (re-home with
              # ack-on-receipt), the tail loss tracks the kill-free
              # tree oracle, and the health ledgers survive.
              and summary["double_folds"] == 0
              and summary["loss_gap_ok"]
              and summary["health_ledger_ok"]
              # With the kills armed the gate must have EXERCISED the
              # failover: at least one re-home/drop, every re-homed
              # device attributed in the ledger, the dead aggregator
              # named by the postmortem, its flight dump on disk.
              and (args.no_faults
                   or (summary["failover_fired"]
                       and summary["rehomed_attributed"]
                       and summary["postmortem_attributed"]
                       and not summary["flight_missing"])))
        return 0 if ok else 1
    if args.chaos_async:
        from colearn_federated_learning_tpu.faults import procsoak

        summary = procsoak.run_async_soak(
            aggregations=args.rounds, n_workers=args.num_workers,
            workdir=args.workdir, round_timeout=args.mp_round_timeout,
            timeout_s=args.mp_timeout, kill=not args.no_faults,
            log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr),
            lock_witness=args.lock_witness,
        )
        print(json.dumps(summary))
        ok = (_lock_witness_ok(summary, args)
              and summary["exit_code"] == 0
              and summary["baseline_exit_code"] == 0
              and summary["aggregations_run"] >= args.rounds
              and summary["baseline_aggregations_run"] >= args.rounds
              # The three async-plane invariants a lost buffer must not
              # break: per-incarnation version monotonicity, an RDP
              # budget that replays to the recorded epsilon (no
              # double-charge through --resume), and a tail loss within
              # tolerance of the same-seed kill-free baseline.
              and summary["version_monotonic"]
              and summary["dp_replay_ok"]
              and summary["loss_gap_ok"]
              and summary["health_ledger_ok"]
              # With the kill armed the gate must have EXERCISED the
              # recovery: a real resume, a postmortem naming the victim,
              # its flight dump on disk, and the injected pump faults
              # attributed in the health ledger.
              and (args.no_faults
                   or (summary["resumed"] >= 1
                       and summary["postmortem_attributed"]
                       and summary["faults_attributed"]
                       and not summary["flight_missing"])))
        return 0 if ok else 1
    if args.agg:
        from colearn_federated_learning_tpu.faults import procsoak

        summary = procsoak.run_agg_soak(
            rounds=args.rounds, n_workers=args.num_workers,
            workdir=args.workdir, round_timeout=args.mp_round_timeout,
            timeout_s=args.mp_timeout, kill=not args.no_faults,
            log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr),
        )
        print(json.dumps(summary))
        ok = (summary["exit_code"] == 0
              and summary["oracle_exit_code"] == 0
              and summary["rounds_run"] == args.rounds
              and summary["oracle_ok"]
              # A gate that never exercised failover proves nothing:
              # with the kill armed, the tree must have re-homed or
              # quorum-dropped at least one slice, the postmortem must
              # attribute the kill, and the flight dump must exist.
              # The tree run's health ledgers must survive the kill.
              and summary["health_ledger_ok"]
              and (args.no_faults
                   or (summary["agg_failovers"] >= 1
                       and summary["postmortem_attributed"]
                       and not summary["flight_missing"])))
        return 0 if ok else 1
    if args.mp:
        from colearn_federated_learning_tpu.faults import procsoak

        kills = ([] if args.no_faults
                 else procsoak.canned_kill_schedule(args.rounds,
                                                    args.num_workers))
        summary = procsoak.run_proc_soak(
            rounds=args.rounds, n_workers=args.num_workers, kills=kills,
            workdir=args.workdir, round_timeout=args.mp_round_timeout,
            timeout_s=args.mp_timeout,
            log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr),
        )
        for k in summary["kills"]:
            print(f"# killed {k['target']} after round "
                  f"{k['fired_after_round']}", file=sys.stderr)
        print(json.dumps(summary))
        need_resume = any(k.target == "coordinator" for k in kills)
        ok = (summary["exit_code"] == 0
              and summary["rounds_run"] == args.rounds
              and summary["weighted_acc"] is not None
              and (summary["rounds_resumed"] >= 1 or not need_resume)
              # Every SIGKILLed process must have left a parseable
              # flight dump behind (heartbeat survivability).
              and not summary["flight_missing"])
        return 0 if ok else 1
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")   # soak is a CPU tool
    except RuntimeError:
        pass
    from colearn_federated_learning_tpu import faults

    if args.secure:
        from colearn_federated_learning_tpu.faults import soak

        if args.no_faults:
            plan = faults.FaultPlan([], seed=0)
        elif args.fault_plan:
            plan = faults.FaultPlan.load(args.fault_plan,
                                         seed=args.fault_seed or None)
        else:
            plan = soak.canned_secure_plan(
                seed=args.fault_seed if args.fault_seed is not None else 11)
        summary = soak.run_secure_soak(
            rounds=args.rounds, n_workers=args.num_workers, plan=plan,
            round_timeout=args.round_timeout,
            log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr),
        )
        print(json.dumps(summary))
        counters = summary["counters"]
        ok = (summary["rounds_run"] == args.rounds
              and summary["oracle_ok"]
              and not summary["skipped_rounds"]
              and counters["privacy.share_recovery_failures_total"] == 0
              # With faults scheduled, recovery must actually have run —
              # a gate that never exercised unmasking proves nothing.
              and (not plan.faults
                   or counters["privacy.masks_recovered_total"] >= 1))
        return 0 if ok else 1
    if args.no_faults:
        plan = None
    elif args.fault_plan:
        plan = faults.FaultPlan.load(args.fault_plan,
                                     seed=args.fault_seed or None)
    else:
        plan = faults.canned_plan(
            seed=args.fault_seed if args.fault_seed is not None else 7)
    config = None
    if args.compress_down and args.compress_down != "none":
        import dataclasses as _dc

        config = faults.default_soak_config(args.num_workers)
        config = _dc.replace(
            config, fed=_dc.replace(config.fed,
                                    compress_down=args.compress_down))
    summary = faults.run_soak(
        rounds=args.rounds, n_workers=args.num_workers, plan=plan,
        round_timeout=args.round_timeout, config=config,
        log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr),
    )
    for t in summary.get("top_faults", [])[:5]:
        print(f"# top fault {t['label']}: {t['count']}", file=sys.stderr)
    print(json.dumps(summary))
    ok = (summary["rounds_run"] == args.rounds
          and summary["weighted_acc"] is not None)
    return 0 if ok else 1


def cmd_fleetsim(args: argparse.Namespace) -> int:
    """Simulated-fleet training: chunked-vmap rounds over a seeded
    synthetic population with an arrival-process traffic model
    (fleetsim/) — per-round records on stderr, summary JSON on stdout."""
    from colearn_federated_learning_tpu import fleetsim
    from colearn_federated_learning_tpu.utils.config import (
        FedConfig,
        ModelConfig,
        RunConfig,
    )

    spec = fleetsim.PopulationSpec(
        num_devices=args.devices, num_classes=args.classes,
        feature_dim=args.feature_dim, shard_capacity=args.capacity,
        label_skew=args.label_skew, seed=args.seed)
    population = fleetsim.DevicePopulation(spec)
    traffic = fleetsim.TrafficModel(
        fleetsim.TrafficSpec(base_rate=args.base_rate,
                             diurnal_amplitude=args.diurnal,
                             round_minutes=args.round_minutes,
                             seed=args.seed),
        spec.num_devices)
    config = ExperimentConfig(
        model=ModelConfig(name="mlp", num_classes=spec.num_classes,
                          hidden_dim=args.hidden_dim, depth=args.depth),
        fed=FedConfig(strategy=args.strategy, local_steps=args.local_steps,
                      batch_size=args.batch_size, lr=args.lr,
                      compress=args.compress,
                      compress_down=args.compress_down or "none",
                      lora_rank=args.lora_rank, lora_alpha=args.lora_alpha),
        run=RunConfig(name="fleetsim", seed=args.seed,
                      learn_observe=bool(args.learn_observe)))
    plan = None
    if args.fault_plan:
        from colearn_federated_learning_tpu import faults

        plan = faults.FaultPlan.load(args.fault_plan,
                                     seed=args.fault_seed or None)
    sim = fleetsim.FleetSim.from_population(
        config, population, traffic, cohort_size=args.cohort,
        chunk_size=args.chunk, fault_plan=plan)
    if args.trace_dir:
        sim.tracer.enabled = True
    if args.async_buffer:
        from colearn_federated_learning_tpu import telemetry

        history = sim.fit_async(
            args.rounds, buffer_size=args.async_buffer,
            max_staleness=args.async_max_staleness,
            prune_after=args.async_prune_after,
            probation=args.async_probation,
            observe=args.async_observe,
            aggregators=args.aggregators,
            log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr))
        last = history[-1]
        # Arrival tracking: what fraction of arrived updates were folded
        # (1 == the fold plane keeps up with the arrival stream; every
        # too-stale discard is tracked work lost).
        arrived = last["arrival_rate_per_min"] * last["sim_time_min"]
        folded = max(0.0, arrived - last["wasted_updates_total"])
        summary = {
            "devices": spec.num_devices,
            "buffer_size": last["buffer_size"],
            "aggregations": len(history),
            "model_version": last["model_version"],
            "sim_minutes": last["sim_time_min"],
            "arrival_rate_per_min": last["arrival_rate_per_min"],
            "agg_rate_per_min": last["agg_rate_per_min"],
            "arrival_tracking": folded / max(arrived, 1e-9),
            "staleness_mean": (
                sum(r["staleness_mean"] for r in history) / len(history)),
            "wasted_updates": last["wasted_updates_total"],
            "train_loss": last["train_loss"],
            "compiles": sim.compile_counts,
        }
        # Staleness tail over every FOLDED update this run (the labeled
        # histogram the observatory keeps) — the distribution, not just
        # the per-aggregation mean.
        hs = telemetry.get_registry().histogram(
            "fleetsim.async_staleness",
            labels={"outcome": "folded"}).summary()
        if hs.get("count"):
            summary["staleness_p50"] = hs["p50"]
            summary["staleness_p90"] = hs["p90"]
            summary["staleness_p99"] = hs["p99"]
        if args.async_buffer == "auto":
            summary["buffer_auto"] = True
        if args.aggregators:
            summary["aggregators"] = args.aggregators
            summary["agg_fold_tracking_min"] = last["agg_fold_tracking_min"]
        if args.async_prune_after:
            summary["pruned"] = last["pruned"]
            summary["pruned_total"] = last["pruned_total"]
        print(json.dumps(summary))
        return 0 if history and last["model_version"] > 0 else 1
    history = sim.fit(
        args.rounds,
        log_fn=lambda rec: print(json.dumps(rec), file=sys.stderr))
    if args.trace_dir:
        from colearn_federated_learning_tpu import telemetry

        path = telemetry.write_tracer(
            args.trace_dir, "fleetsim", sim.tracer,
            metrics=telemetry.get_registry().snapshot())
        print(f"trace written to {path}", file=sys.stderr)
    wall = sum(r["round_time_s"] for r in history) or 1e-9
    clients = sum(r["clients_trained"] for r in history)
    summary = {
        "devices": spec.num_devices,
        "cohort": args.cohort,
        "chunk": sim.chunk_size,
        "rounds": len(history),
        "clients_trained": clients,
        "rounds_per_sec": len(history) / wall,
        "clients_per_sec": clients / wall,
        "bytes_up_per_round": (
            sum(r["bytes_up_est"] for r in history) / len(history)),
        "bytes_down_per_round": (
            sum(r["bytes_down_est"] for r in history) / len(history)),
        "dropped": sum(r["dropped"] for r in history),
        "straggled": sum(r["straggled"] for r in history),
        "corrupted": sum(r["corrupted"] for r in history),
        "train_loss": history[-1]["train_loss"],
        # One entry per jitted executable; "chunk" staying at 1 across a
        # whole sweep is the pad-to-fixed-width invariant, machine-checked.
        "compiles": sim.compile_counts,
    }
    print(json.dumps(summary))
    return 0 if history and clients > 0 else 1


def cmd_lint(args: argparse.Namespace) -> int:
    """Run the AST lint (analysis/) — CPU-only, never initializes jax."""
    import os

    from colearn_federated_learning_tpu.analysis import engine as lint_engine
    from colearn_federated_learning_tpu.analysis import reporters

    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    if args.root:
        root = os.path.abspath(args.root)
    else:
        root = next(
            (c for c in (os.getcwd(), os.path.dirname(pkg_dir))
             if os.path.exists(os.path.join(c, "pyproject.toml"))),
            os.getcwd())
    config = lint_engine.LintConfig.from_pyproject(root)
    if args.rules:
        config.enable = [r.strip() for r in args.rules.split(",")]
    if args.disable:
        config.disable = tuple(
            r.strip() for r in args.disable.split(","))
    try:
        eng = lint_engine.LintEngine(config=config, root=root)
    except ValueError as e:
        print(f"colearn lint: {e}", file=sys.stderr)
        return 2
    paths = args.paths or [pkg_dir]
    baseline_path = (os.path.join(root, args.baseline)
                     if args.baseline else None)
    if args.write_baseline:
        # Lint without the current baseline, then accept everything found.
        result = eng.run(paths, baseline_path="")
        target = baseline_path or os.path.join(root, config.baseline)
        entries = lint_engine.write_baseline(target, result.findings)
        print(f"colearn lint: baselined {len(result.findings)} finding(s) "
              f"({len(entries)} fingerprint(s)) -> {target}")
        return 0
    if args.gate:
        # CI posture: the baseline is a MIGRATION vehicle, not a place
        # findings live.  The gate fails when any fingerprint is still
        # parked there, so every suppression is an inline, reasoned noqa.
        gate_baseline = baseline_path or os.path.join(root, config.baseline)
        entries = lint_engine.load_baseline(gate_baseline)
        if entries:
            print(f"colearn lint --gate: baseline {gate_baseline} still "
                  f"carries {len(entries)} fingerprint(s); fix the "
                  f"findings or move each to an inline "
                  f"`# colearn: noqa(CLxxx): <reason>`", file=sys.stderr)
            return 1
    result = eng.run(paths, baseline_path=baseline_path)
    if args.format == "json":
        print(reporters.render_json(result))
    elif args.format == "sarif":
        print(reporters.render_sarif(result))
    else:
        print(reporters.render_text(result))
    return result.exit_code


def cmd_trace_summary(args: argparse.Namespace) -> int:
    from colearn_federated_learning_tpu import telemetry

    if os.path.isdir(args.trace_file):
        # A --profile-dir: device time by the program's own scopes.
        try:
            print(telemetry.summarize_profile(args.trace_file,
                                              top=args.top))
        except (OSError, ValueError, KeyError) as e:
            print(f"cannot read profile {args.trace_file}: {e}",
                  file=sys.stderr)
            return 2
        return 0
    try:
        doc = telemetry.load_trace(args.trace_file)
    except (OSError, ValueError) as e:
        print(f"cannot read trace {args.trace_file}: {e}", file=sys.stderr)
        return 2
    print(telemetry.summarize_trace(doc, root=args.root))
    return 0


def cmd_postmortem(args: argparse.Namespace) -> int:
    """Merge crash flight dumps with the round WAL into one causal report:
    who died, of what, at which round, and which rounds were in flight
    (logged but not yet durable in a checkpoint)."""
    import os

    from colearn_federated_learning_tpu import telemetry

    dumps = telemetry.load_flight_dumps(args.flight_dir)
    wal_entries = None
    if args.wal_dir:
        from colearn_federated_learning_tpu.ckpt.wal import RoundWal

        wal_dir = args.wal_dir
        if os.path.isfile(wal_dir):           # accept the file path too
            wal_dir = os.path.dirname(wal_dir) or "."
        wal_entries = RoundWal(wal_dir).load()
    report = telemetry.postmortem_report(
        dumps, wal_entries=wal_entries,
        checkpoint_step=args.checkpoint_step)
    if args.format == "json":
        print(json.dumps(report))
    else:
        print(telemetry.render_postmortem(report))
    return 0 if dumps else 1


def cmd_top(args: argparse.Namespace) -> int:
    """Terminal dashboard over a live /snapshot.json endpoint: round
    rate, cohort health, fault counters, compile churn, HBM."""
    import time
    import urllib.error
    import urllib.request

    from colearn_federated_learning_tpu.telemetry import runtime

    url = args.url or f"http://127.0.0.1:{args.port}/snapshot.json"
    prev = None
    while True:
        try:
            with urllib.request.urlopen(url, timeout=5.0) as resp:
                snap = json.loads(resp.read().decode("utf-8"))
        except (OSError, urllib.error.URLError, ValueError) as e:
            print(f"colearn top: cannot fetch {url}: {e}", file=sys.stderr)
            return 1
        body = runtime.render_top(
            snap, prev=prev,
            interval_s=args.interval if prev is not None else 0.0)
        if args.once:
            print(body)
            return 0
        # Clear + home instead of curses: works in any terminal and in
        # script(1) captures.
        sys.stdout.write("\x1b[2J\x1b[H" + body + "\n")
        sys.stdout.flush()
        prev = snap
        time.sleep(args.interval)


def cmd_sentinel(args: argparse.Namespace) -> int:
    """Evaluate the [tool.colearn.slo] rules against committed results/
    benchmark JSONL — exit non-zero on any violation (the CI perf gate)."""
    import os

    from colearn_federated_learning_tpu.analysis import sentinel

    pkg_dir = os.path.dirname(os.path.abspath(__file__))
    if args.root:
        root = os.path.abspath(args.root)
    else:
        root = next(
            (c for c in (os.getcwd(), os.path.dirname(pkg_dir))
             if os.path.exists(os.path.join(c, "pyproject.toml"))),
            os.getcwd())
    try:
        verdict = sentinel.evaluate_slo(root)
    except ValueError as e:
        print(f"colearn sentinel: {e}", file=sys.stderr)
        return 2
    if args.format == "json":
        print(json.dumps(verdict))
    else:
        print(sentinel.render_verdict(verdict))
    return 0 if verdict["ok"] else 1


def cmd_health(args: argparse.Namespace) -> int:
    """Render the per-device health ledger a --health-dir run wrote: top
    offenders, straggler latency tail, per-aggregator slice skew."""
    from colearn_federated_learning_tpu import telemetry

    try:
        devices = telemetry.load_health(args.health_dir)
    except (OSError, ValueError) as e:
        print(f"colearn health: cannot read {args.health_dir}: {e}",
              file=sys.stderr)
        return 2
    if args.format == "json":
        print(json.dumps({d: h.to_dict() for d, h in devices.items()}))
    else:
        print(telemetry.render_health(devices, top=args.top))
    return 0 if devices else 1


def cmd_converge(args: argparse.Namespace) -> int:
    """Round-over-round learning report from committed JSONL: any file
    or results dir whose records carry conv_* keys (a --learn-observe
    run, an event stream, a bench log)."""
    import glob
    from colearn_federated_learning_tpu import telemetry

    paths = ([args.results] if os.path.isfile(args.results)
             else sorted(glob.glob(
                 os.path.join(args.results, "**", "*.jsonl"),
                 recursive=True)))
    records: list = []
    for path in paths:
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if isinstance(rec, dict):
                        records.append(rec)
        except OSError as e:
            print(f"colearn converge: cannot read {path}: {e}",
                  file=sys.stderr)
            return 2
    if not paths:
        print(f"colearn converge: no JSONL under {args.results}",
              file=sys.stderr)
        return 2
    report = telemetry.render_convergence_report(records)
    print(report)
    return 0 if not report.startswith("no learning signals") else 1


def cmd_configs(_args: argparse.Namespace) -> int:
    for name, cfg in sorted(CONFIGS.items()):
        print(f"{name}: {cfg.model.name} on {cfg.data.dataset}, "
              f"{cfg.data.num_clients} clients, {cfg.fed.strategy}")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="colearn")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_train = sub.add_parser("train", help="run federated training")
    _add_override_flags(p_train)
    p_train.add_argument("--role", choices=["sim", "client"], default="sim")
    p_train.add_argument("--client-id", type=int, default=None)
    p_train.add_argument("--global-model", default=None,
                         help="global model npz (client role)")
    p_train.add_argument("--out", default=None,
                         help="update npz to write (client role)")
    p_train.add_argument("--residual-path", default=None,
                         help="client role: persist the uplink error-"
                              "feedback compression residual here across "
                              "file-plane rounds (--compress-feedback)")
    p_train.add_argument("--resume", action="store_true")
    p_train.add_argument("--per-client-eval", action="store_true",
                         help="report per-client accuracy spread at the end")
    p_train.add_argument("--personalize-steps", type=int, default=0,
                         help="fine-tune-then-eval personalization probe: "
                              "N local SGD steps per client on half its "
                              "shard, scored on the held-out half")
    p_train.add_argument("--detection-eval", action="store_true",
                         help="detection-oriented held-out report "
                              "(per-class P/R/F1, alarm detection/"
                              "false-alarm rates — the IoT anomaly "
                              "metrics; class 0 = benign)")
    p_train.set_defaults(fn=cmd_train)

    p_init = sub.add_parser("init", help="write an initial global model file")
    _add_override_flags(p_init)
    p_init.add_argument("--out", required=True)
    p_init.set_defaults(fn=cmd_init)

    p_agg = sub.add_parser("aggregate",
                           help="fold client update files into a new global model")
    _add_override_flags(p_agg)
    p_agg.add_argument("--global-model", required=True)
    p_agg.add_argument("--updates", nargs="+", required=True)
    p_agg.add_argument("--out", required=True)
    p_agg.set_defaults(fn=cmd_aggregate)

    p_eval = sub.add_parser("eval", help="evaluate a global model file")
    _add_override_flags(p_eval)
    p_eval.add_argument("--global-model", required=True)
    p_eval.add_argument("--detection-eval", action="store_true",
                        help="add the anomaly-detection report (per-class "
                             "P/R/F1, alarm detection/false-alarm rates)")
    p_eval.set_defaults(fn=cmd_eval)

    sub.add_parser("configs", help="list experiment configs").set_defaults(
        fn=cmd_configs)
    p_broker = sub.add_parser("broker", help="run the pub/sub control-plane "
                                             "broker (MQTT equivalent)")
    p_broker.add_argument("--host", default="127.0.0.1")
    p_broker.add_argument("--port", type=int, default=0)
    _add_observability_flags(p_broker)
    p_broker.set_defaults(fn=cmd_broker)

    p_worker = sub.add_parser("worker", help="run a device worker process "
                                             "(shard + local trainer)")
    _add_override_flags(p_worker)
    p_worker.add_argument("--client-id", type=int, default=None)
    p_worker.add_argument("--broker-host", default="127.0.0.1")
    p_worker.add_argument("--broker-port", type=int, required=True)
    p_worker.add_argument("--mud-profile", default=None,
                          help="path to this device's RFC 8520 MUD JSON, "
                               "announced on enrollment (comm/mud.py)")
    _add_observability_flags(p_worker)
    p_worker.set_defaults(fn=cmd_worker)

    p_aggtier = sub.add_parser(
        "aggregator",
        help="run one aggregator-tree process: folds its cohort slice "
             "and ships one partial sum to the coordinator "
             "(comm/aggregator.py)")
    _add_override_flags(p_aggtier)
    p_aggtier.add_argument("--agg-id", type=int, default=None)
    p_aggtier.add_argument("--broker-host", default="127.0.0.1")
    p_aggtier.add_argument("--broker-port", type=int, required=True)
    p_aggtier.add_argument("--heartbeat", type=float, default=0.5,
                           help="retained-announce heartbeat period (s); "
                                "the coordinator's liveness signal")
    _add_observability_flags(p_aggtier)
    p_aggtier.set_defaults(fn=cmd_aggregator)

    p_coord = sub.add_parser("coordinate",
                             help="run the federated coordinator over "
                                  "enrolled workers")
    _add_override_flags(p_coord)
    p_coord.add_argument("--broker-host", default="127.0.0.1")
    p_coord.add_argument("--broker-port", type=int, required=True)
    p_coord.add_argument("--min-devices", type=int, default=2)
    p_coord.add_argument("--enroll-timeout", type=float, default=60.0)
    p_coord.add_argument("--round-timeout", type=float, default=120.0)
    p_coord.add_argument("--no-evaluator", action="store_true")
    p_coord.add_argument("--elastic", action="store_true",
                         help="admit late-joining workers between rounds")
    p_coord.add_argument("--resume", action="store_true",
                         help="restore the latest checkpoint from "
                              "--checkpoint-dir before training")
    p_coord.add_argument("--per-client-eval", action="store_true",
                         help="report each trainer's own-shard accuracy "
                              "after training (worker self_eval op)")
    p_coord.add_argument("--per-type", action="store_true",
                         help="one federation per MUD device type (the "
                              "CoLearn topology; comm/per_type.py) — "
                              "each type trains its own global model")
    p_coord.add_argument("--min-per-type", type=int, default=2,
                         help="smallest device class that gets its own "
                              "federation under --per-type")
    p_coord.add_argument("--mud-require-profile", action="store_true",
                         help="refuse devices that enroll without an RFC "
                              "8520 MUD profile (comm/mud.py)")
    p_coord.add_argument("--mud-allowed-types", default=None,
                         help="comma-separated device types admitted to "
                              "the federation (MUD colearn:device-type)")
    p_coord.add_argument("--async-buffer", type=_async_buffer_arg,
                         default=0,
                         help="> 0 switches to buffered-asynchronous "
                              "aggregation (FedBuff-style): apply the "
                              "staleness-weighted mean every N updates "
                              "instead of running synchronous rounds; "
                              "'auto' sizes N from the observed arrival "
                              "rate (target fold cadence)")
    p_coord.add_argument("--async-observe", action="store_true",
                         help="stamp observatory keys (contribution "
                              "mass, arrival rate, staleness tail) into "
                              "async aggregation records (implied by "
                              "--async-buffer auto)")
    p_coord.add_argument("--async-prune-after", type=int, default=0,
                         help="pause a device's dispatch pump after this "
                              "many CONSECUTIVE too-stale discards "
                              "(straggler pruning; needs --health-dir)")
    p_coord.add_argument("--async-prune-score", type=float, default=0.0,
                         help="pause pumps whose health-ledger score "
                              "(failure weights + latency-vs-median "
                              "term) reaches this; 0 disables "
                              "(needs --health-dir)")
    p_coord.add_argument("--async-probation", type=int, default=8,
                         help="aggregations a pruned device sits out "
                              "before probation re-admits its pump")
    _add_observability_flags(p_coord)
    p_coord.set_defaults(fn=cmd_coordinate)

    p_chaos = sub.add_parser("chaos",
                             help="run an in-process chaos soak: a tiny "
                                  "federation under an injected fault "
                                  "plan, reporting recovery counters")
    p_chaos.add_argument("--rounds", type=int, default=10)
    p_chaos.add_argument("--num-workers", type=int, default=4)
    p_chaos.add_argument("--round-timeout", type=float, default=6.0,
                         help="per-round deadline for the FAULTED rounds "
                              "(the warmup round gets a generous one)")
    p_chaos.add_argument("--fault-plan", default=None,
                         help="JSON fault-plan file; default is the "
                              "canned acceptance plan (faults/soak.py)")
    p_chaos.add_argument("--fault-seed", type=int, default=None)
    p_chaos.add_argument("--no-faults", action="store_true",
                         help="run the soak without any plan (baseline)")
    p_chaos.add_argument("--compress-down", default=None,
                         choices=["none", "int8", "topk"],
                         help="soak with downlink delta compression on "
                              "(exercises the cache-miss resync path "
                              "under faults)")
    p_chaos.add_argument("--secure", action="store_true",
                         help="secure-aggregation exactness gate: DH "
                              "masked federation vs plain-FedAvg oracle "
                              "in lockstep under the dropout plan; fails "
                              "unless every round's recovered sum matches "
                              "the oracle (faults/soak.run_secure_soak)")
    p_chaos.add_argument("--mp", action="store_true",
                         help="multi-process soak: broker/coordinator/"
                              "workers as real subprocesses, real SIGKILL "
                              "on the canned schedule (coordinator "
                              "included — exercises --resume recovery)")
    p_chaos.add_argument("--agg", action="store_true",
                         help="aggregator-tree failover gate: a real "
                              "2-aggregator federation with one "
                              "aggregator SIGKILLed mid-round, final "
                              "params lockstep vs a flat oracle run "
                              "(faults/procsoak.run_agg_soak)")
    p_chaos.add_argument("--async", dest="chaos_async",
                         action="store_true",
                         help="buffered-async chaos gate: broker/workers/"
                              "async coordinator as real subprocesses, "
                              "SIGKILL mid-aggregation + --resume "
                              "relaunch; gates version monotonicity, "
                              "accountant replay, and final loss vs a "
                              "same-seed kill-free async run "
                              "(faults/procsoak.run_async_soak)")
    p_chaos.add_argument("--tree-async", dest="chaos_tree_async",
                         action="store_true",
                         help="buffered-async THROUGH the aggregator "
                              "tree: 2 per-slice aggregator buffers, "
                              "aggregator 0 SIGKILLed mid-aggregation "
                              "(stays dead — its in-flight slice must "
                              "re-home to the sibling with zero double-"
                              "folds) plus a broker kill-and-rebind; "
                              "tail-loss parity vs a same-seed kill-free "
                              "tree oracle "
                              "(faults/procsoak.run_tree_async_soak)")
    p_chaos.add_argument("--ckpt", action="store_true",
                         help="streaming-checkpoint chaos gate: a tp=2 "
                              "--ckpt-stream federation is SIGKILLed "
                              "mid-save (shard files down, manifest not "
                              "yet committed) and must --resume on tp=1 "
                              "from the last COMMITTED generation, "
                              "bitwise (digest match across the "
                              "re-shard), with loss parity vs a "
                              "kill-free oracle; with --no-faults runs "
                              "the kill-free cross-tp bitwise smoke "
                              "(faults/procsoak.run_ckpt_soak)")
    p_chaos.add_argument("--lock-witness", action="store_true",
                         help="(--async/--tree-async) run every fleet "
                              "process with the runtime lock witness "
                              "(faults/lockwitness) armed and gate on "
                              "zero observed ordering inversions and "
                              "zero unguarded guarded-structure "
                              "accesses")
    p_chaos.add_argument("--workdir", default=None,
                         help="--mp scratch dir for checkpoints + process "
                              "logs (default: a fresh temp dir)")
    p_chaos.add_argument("--mp-round-timeout", type=float, default=120.0,
                         help="--mp per-round deadline (covers the first "
                              "round's jit compile in every worker)")
    p_chaos.add_argument("--mp-timeout", type=float, default=600.0,
                         help="--mp whole-soak wall-clock backstop; a hung "
                              "federation is killed and reported")
    p_chaos.set_defaults(fn=cmd_chaos)

    p_fleet = sub.add_parser("fleetsim",
                             help="simulate a 1k-1M device fleet: chunked "
                                  "vmap rounds over a synthetic population "
                                  "with a traffic model (fleetsim/)")
    p_fleet.add_argument("--devices", type=int, default=10_000)
    p_fleet.add_argument("--cohort", type=int, default=1024)
    p_fleet.add_argument("--rounds", type=int, default=5)
    p_fleet.add_argument("--chunk", type=int, default=1024,
                         help="vmap chunk size: memory is O(chunk), wall "
                              "time is O(cohort/chunk) dispatches")
    p_fleet.add_argument("--seed", type=int, default=0)
    p_fleet.add_argument("--classes", type=int, default=10)
    p_fleet.add_argument("--feature-dim", type=int, default=32)
    p_fleet.add_argument("--capacity", type=int, default=32,
                         help="padded per-device shard size")
    p_fleet.add_argument("--label-skew", type=float, default=0.7,
                         help="P(label == device home class); non-IID knob")
    p_fleet.add_argument("--base-rate", type=float, default=2.0,
                         help="mean device check-ins per hour")
    p_fleet.add_argument("--diurnal", type=float, default=0.8,
                         help="day/night availability swing in [0, 1]")
    p_fleet.add_argument("--round-minutes", type=float, default=10.0)
    p_fleet.add_argument("--strategy", default="fedavg",
                         choices=["fedavg", "fedprox", "fedadam", "fedyogi"])
    p_fleet.add_argument("--local-steps", type=int, default=4)
    p_fleet.add_argument("--batch-size", type=int, default=16)
    p_fleet.add_argument("--lr", type=float, default=0.05)
    p_fleet.add_argument("--hidden-dim", type=int, default=64)
    p_fleet.add_argument("--depth", type=int, default=2)
    p_fleet.add_argument("--compress", default="none",
                         choices=["none", "int8", "topk", "topk8"],
                         help="uplink scheme for the byte estimates")
    p_fleet.add_argument("--lora-rank", type=int, default=0,
                         help="rank-r adapter federation: price the "
                              "factor-frame uplink (bytes_up_saved_est; "
                              "training dynamics stay dense in the sim)")
    p_fleet.add_argument("--lora-alpha", type=float, default=16.0)
    p_fleet.add_argument("--compress-down", default="none",
                         choices=["none", "int8", "topk"])
    p_fleet.add_argument("--fault-plan", default=None,
                         help="JSON fault plan; (device, round, op='train') "
                              "keys drive per-simulated-device drop/"
                              "straggle/corrupt")
    p_fleet.add_argument("--fault-seed", type=int, default=None)
    p_fleet.add_argument("--trace-dir", default=None,
                         help="write the sweep's span trace (fleet_round/"
                              "train_chunks/train_chunk) as a Chrome-trace "
                              "JSON here; read with `colearn trace-summary`")
    p_fleet.add_argument("--async-buffer", type=_async_buffer_arg,
                         default=0,
                         help="> 0 runs the buffered-ASYNC simulation "
                              "instead of sync rounds: fold every N "
                              "arrival-ordered completions with staleness "
                              "weighting (FleetSim.fit_async); --rounds "
                              "then counts aggregations; 'auto' sizes N "
                              "from the observed arrival rate")
    p_fleet.add_argument("--async-observe", action="store_true",
                         help="async mode: stamp observatory keys "
                              "(staleness tail, contribution mass, EWMA "
                              "arrival rate) into records (implied by "
                              "--async-buffer auto)")
    p_fleet.add_argument("--async-max-staleness", type=int, default=10,
                         help="async mode: discard updates staler than "
                              "this many versions (wasted compute)")
    p_fleet.add_argument("--async-prune-after", type=int, default=0,
                         help="async mode: stop re-dispatching a device "
                              "after this many CONSECUTIVE too-stale "
                              "discards (0 = off)")
    p_fleet.add_argument("--aggregators", type=int, default=0,
                         help="async mode: two-tier tree — devices "
                              "sliced by service time across N per-"
                              "slice auto-K buffers, partials folded "
                              "unscaled at the edge and staleness-"
                              "discounted at the root against the "
                              "OLDEST constituent (0 = flat async)")
    p_fleet.add_argument("--async-probation", type=int, default=8,
                         help="async mode: aggregations a pruned device "
                              "sits out before re-admission")
    p_fleet.add_argument("--learn-observe", action="store_true",
                         help="convergence observatory: stamp conv_* "
                              "learning-health keys (update norm / cosine "
                              "/ trend, per-cohort drift skew) on round "
                              "records; `colearn converge` renders them")
    p_fleet.set_defaults(fn=cmd_fleetsim)

    p_lint = sub.add_parser("lint",
                            help="run the AST invariant checks "
                                 "(CL001-CL010; analysis/) — fast, "
                                 "CPU-only, no jax init")
    p_lint.add_argument("paths", nargs="*",
                        help="files/dirs to lint (default: the installed "
                             "package)")
    p_lint.add_argument("--format", choices=["text", "json", "sarif"],
                        default="text")
    p_lint.add_argument("--gate", action="store_true",
                        help="CI gate: additionally fail when the "
                             "baseline file still carries accepted "
                             "fingerprints — every suppression must be "
                             "an inline reasoned noqa")
    p_lint.add_argument("--rules", default=None,
                        help="comma-separated rule ids to run "
                             "(default: all registered)")
    p_lint.add_argument("--disable", default=None,
                        help="comma-separated rule ids to skip")
    p_lint.add_argument("--baseline", default=None,
                        help="baseline JSON path relative to --root "
                             "(default: [tool.colearn.lint].baseline)")
    p_lint.add_argument("--write-baseline", action="store_true",
                        help="accept every current finding into the "
                             "baseline file and exit 0")
    p_lint.add_argument("--root", default=None,
                        help="repo root holding pyproject.toml + baseline "
                             "(default: cwd, else the package parent)")
    p_lint.set_defaults(fn=cmd_lint)

    p_trace = sub.add_parser("trace-summary",
                             help="print a per-phase time breakdown of a "
                                  "--trace-dir Chrome-trace JSON file, or "
                                  "of a --profile-dir the device time by "
                                  "the program's own scopes")
    p_trace.add_argument("trace_file",
                         help="path to the *_trace.json file, or to a "
                              "--profile-dir directory")
    p_trace.add_argument("--root", default="round",
                         help="span name used as the per-round denominator")
    p_trace.add_argument("--top", type=int, default=15,
                         help="scope paths listed per program "
                              "(--profile-dir)")
    p_trace.set_defaults(fn=cmd_trace_summary)

    p_pm = sub.add_parser("postmortem",
                          help="merge crash flight dumps (--flight-dir) "
                               "with the round WAL into a who-died-where "
                               "report")
    p_pm.add_argument("flight_dir",
                      help="directory holding flight_<pid>.json dumps "
                           "(searched recursively)")
    p_pm.add_argument("--wal-dir", default=None,
                      help="checkpoint dir holding round_wal.jsonl (or "
                           "the file itself) to reconcile rounds against")
    p_pm.add_argument("--checkpoint-step", type=int, default=None,
                      help="latest durable checkpoint round; WAL entries "
                           "past it count as in flight, not committed")
    p_pm.add_argument("--format", choices=["text", "json"], default="text")
    p_pm.set_defaults(fn=cmd_postmortem)

    p_top = sub.add_parser("top",
                           help="live terminal view of a --metrics-port "
                                "process: round rate, cohort health, "
                                "faults, compiles, HBM")
    p_top.add_argument("--port", type=int, default=9100,
                       help="metrics port of the process to watch")
    p_top.add_argument("--url", default=None,
                       help="full /snapshot.json URL (overrides --port)")
    p_top.add_argument("--interval", type=float, default=2.0,
                       help="refresh period in seconds")
    p_top.add_argument("--once", action="store_true",
                       help="print one snapshot and exit (no screen clear)")
    p_top.set_defaults(fn=cmd_top)

    p_slo = sub.add_parser("sentinel",
                           help="evaluate [tool.colearn.slo] rules against "
                                "results/*.jsonl; non-zero exit on any "
                                "regression (the CI perf gate)")
    p_slo.add_argument("--root", default=None,
                       help="repo root holding pyproject.toml and the "
                            "rule-referenced result files (default: cwd, "
                            "else the package parent)")
    p_slo.add_argument("--format", choices=["text", "json"], default="text")
    p_slo.set_defaults(fn=cmd_sentinel)

    p_health = sub.add_parser("health",
                              help="per-device fleet health from a "
                                   "--health-dir run: top offenders, "
                                   "straggler tail, per-aggregator skew")
    p_health.add_argument("health_dir",
                          help="directory holding health_*.jsonl ledgers "
                               "(searched recursively)")
    p_health.add_argument("--top", type=int, default=10,
                          help="offender rows to show")
    p_health.add_argument("--format", choices=["text", "json"],
                          default="text")
    p_health.set_defaults(fn=cmd_health)

    p_conv = sub.add_parser("converge",
                            help="round-over-round learning report from "
                                 "a --learn-observe run's JSONL (update "
                                 "norm / cosine / trend per round)")
    p_conv.add_argument("results",
                        help="JSONL file, or directory searched "
                             "recursively for *.jsonl")
    p_conv.set_defaults(fn=cmd_converge)

    args = parser.parse_args(argv)
    # Every subcommand, so the roles one federation spawns (broker,
    # coordinator, workers, aggregators) share one compile cache.
    enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
