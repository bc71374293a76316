"""The fleet-simulation hot path: chunked ``jax.vmap`` over local_update.

One simulated round is exactly the engine's round — same per-(client,
round) PRNG keys (utils/prng.py), same FedAvg weighting
(``num_examples * contrib``), same mean + ``strategies.server_update``
epilogue — but the cohort is processed in FIXED-SIZE chunks:

    cohort -> [chunk_0 | chunk_1 | ...]      (last chunk zero-padded)
    chunk_i: vmap(local_update) -> weighted partial sums (on device)
    fold:    partial sums add into the round accumulator (on device)

Memory is therefore O(chunk x model + chunk x shard) at ANY cohort
size: a million-client round is ~250 chunk dispatches, not a million-
row vmap.  Chunk partial sums fold with the same ``tree_weighted_sum``
semantics the engine aggregates with, so a one-chunk round reproduces
the engine bit-for-bit (tests/test_fleetsim.py parity tests).

Faults reuse the FaultPlan key space ``(device, round, op)`` with
``op="train"`` (faults/plan.py):

- ``drop_request``    — the device never trains or reports (no uplink);
- ``delay``           — straggle: the device loses ``ms`` of its
  simulated round deadline, its ``step_budget`` shrinks proportionally
  (fed/local.py masks the lost steps; below the completion threshold
  its FedAvg weight zeroes exactly like an engine straggler);
- ``corrupt_payload`` — the update arrives corrupted and is discarded
  (uplink bytes spent, weight zeroed — the CRC-reject analog).

NOTE on plan authoring: ``FaultSpec.count`` defaults to 1 (one firing
TOTAL); fleet-wide schedules want explicit ``count=0`` (unlimited) or a
budget sized to the cohort.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.fed import compression
from colearn_federated_learning_tpu.fed import setup as setup_lib
from colearn_federated_learning_tpu.fed import strategies
from colearn_federated_learning_tpu.fed.programs import draw_cohort
from colearn_federated_learning_tpu.utils import prng, pytrees
from colearn_federated_learning_tpu.utils.config import ExperimentConfig
from colearn_federated_learning_tpu.utils.serialization import (
    wire_frame_length,
)

_FLEET_FAULT_KINDS = ("drop_request", "delay", "corrupt_payload")


def _validate_fleet_config(config: ExperimentConfig) -> None:
    """The fleet path is the engine's plain weighted-mean FedAvg family;
    the stateful/privacy variants keep their engine-only homes."""
    setup_lib.require_stateless_strategy(config, "fleetsim")
    setup_lib.require_mean_aggregator(config, "fleetsim")
    c = config.fed
    if c.dp_clip > 0.0 or c.secure_agg:
        raise NotImplementedError(
            "fleetsim does not support dp/secure-agg hooks yet: their "
            "noise accounting and mask pairing assume the engine's "
            "single-program cohort; run the on-device engine")


def _count_fault(kind: str) -> None:
    """Fault-plane telemetry, aggregate only: the comm injector labels
    ``fault.injected_total`` per device, but at fleet scale per-device
    label children would grow the registry O(cohort) per round."""
    reg = telemetry.get_registry()
    reg.counter("fault.injected_total", labels={"kind": kind}).inc()
    reg.counter(f"fault.injected.{kind}").inc()


class FleetSim:
    """Chunked-vmap fleet simulator.

    Build with :meth:`from_population` (synthetic fleet + traffic model,
    the 1k->1M workload) or :meth:`from_learner` (wrap an existing
    :class:`~fed.engine.FederatedLearner`'s data/trainer/keys — the
    parity harness the tests trust the vmapped path against).
    """

    def __init__(
        self,
        *,
        config: ExperimentConfig,
        local_update: Callable,
        num_steps: int,
        base_key,
        server_state,
        shard_fn: Callable[[np.ndarray], tuple],
        budget_fn: Callable[[np.ndarray], np.ndarray],
        select_fn: Callable[[int], np.ndarray],
        num_devices: int,
        cohort_size: int,
        chunk_size: int = 1024,
        fault_plan=None,
        round_deadline_ms: float = 1000.0,
        available_fraction_fn: Optional[Callable[[int], float]] = None,
    ):
        _validate_fleet_config(config)
        self.config = config
        self.local_update = local_update
        self.num_steps = int(num_steps)
        self.base_key = base_key
        self.server_state = server_state
        self._shard_fn = shard_fn
        self._budget_fn = budget_fn
        self._select_fn = select_fn
        self.num_devices = int(num_devices)
        self.cohort_size = int(min(cohort_size, num_devices))
        self.chunk_size = int(min(chunk_size, max(1, self.cohort_size)))
        self.fault_plan = fault_plan
        self.round_deadline_ms = float(round_deadline_ms)
        self._available_fraction_fn = available_fraction_fn
        # Set by from_population; fit_async needs per-device arrival
        # rates, not just the fleet-mean fraction.
        self._traffic = None
        self.history: list[dict] = []
        self.tracer = telemetry.Tracer(process="fleetsim", enabled=False)
        # Per-device health feed (telemetry/health.py): the simulated
        # fleet attributes its injected faults to devices exactly like
        # the socket planes attribute real ones.  Off by default.
        self.health = None
        if config.run.health_dir:
            self.health = telemetry.HealthLedger(config.run.health_dir,
                                                 "fleetsim")
        # Convergence observatory (telemetry/convergence.py): updates are
        # simulation-local, so this plane legitimately sees per-device
        # norms and per-cohort centroids — the attribution secure
        # aggregation denies the socket planes.  Off by default: no
        # observatory, no obs program, round records byte-identical.
        self._learn = None
        self._obs_chunk_fn = None
        self._population = None           # set by from_population
        if config.run.learn_observe:
            self._learn = telemetry.ConvergenceObservatory()

        # CompileTracker on every jitted program makes the "one compile
        # per sweep shape" claim a measurable invariant (compile_counts
        # below; test-pinned): zero-padding to a fixed chunk width means
        # the chunk fn must hold exactly ONE signature per sweep.
        self._chunk_fn = telemetry.CompileTracker(
            self._build_chunk_fn(), name="fleetsim.chunk")
        self._finish_fn = telemetry.CompileTracker(
            self._build_finish_fn(), name="fleetsim.finish")
        # One fused add per fold: the 4 partial sums are one pytree.
        self._fold_fn = telemetry.CompileTracker(
            jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b)),
            name="fleetsim.fold")

        # Wire-cost model (comm codecs, shape-only so computed ONCE):
        # frame lengths depend on leaf shapes/dtypes, not values.
        params_np = jax.tree.map(np.asarray, server_state.params)
        zeros = jax.tree.map(np.zeros_like, params_np)
        self.down_full_bytes = int(wire_frame_length(
            params_np, {"round": 0, "down": "full"}))
        scheme_down = config.fed.compress_down
        if scheme_down == "none":
            self.down_frame_bytes = self.down_full_bytes
        else:
            wire, meta = compression.compress_delta(zeros, scheme_down)
            self.down_frame_bytes = int(wire_frame_length(
                wire, {"round": 0, "down": "delta", **meta}))
        # LoRA pricing (fed/lora.py): with fed.lora_rank > 0 the real
        # wire planes ship FACTOR frames on the uplink, so the byte
        # estimator prices those.  The simulated training dynamics stay
        # dense (the chunked vmap trainer is unchanged) — only the
        # wire-cost model is adapter-aware, the same shape-only
        # decoupling as the codec pricing above.
        if config.fed.lora_rank > 0:
            from colearn_federated_learning_tpu.fed import lora as lora_lib

            up_zeros = jax.tree.map(np.asarray, lora_lib.init_factors(
                params_np, config.fed.lora_rank,
                model_name=config.model.name))
        else:
            up_zeros = zeros
        wire_up, meta_up = compression.compress_delta(
            up_zeros, config.fed.compress,
            topk_fraction=config.fed.topk_fraction)
        self.up_frame_bytes = int(wire_frame_length(
            wire_up, {"round": 0, "op": "train", **meta_up}))
        # Uplink fast-path savings (PR 10): per-update bytes a compressed
        # (or factor-only) uplink saves vs the dense train frame — same
        # shape-only pricing the coordinator's comm.bytes_saved_uplink
        # counter uses.
        if config.fed.compress == "none" and config.fed.lora_rank == 0:
            self.up_saved_bytes = 0
        else:
            dense_up = int(wire_frame_length(
                zeros, {"round": 0, "op": "train", "compress": "none"}))
            self.up_saved_bytes = max(0, dense_up - self.up_frame_bytes)
        # Sharded-downlink shape (PR 9): with run.tp_size > 1 the server
        # encodes each broadcast from per-device shards, never
        # materializing a replicated copy.  The frame bytes are identical
        # (same payload); what the estimator learns is the per-encode
        # gather bytes AVOIDED — pure shape math from the partition rules,
        # so 1M-cohort sweeps reflect the sharded wire cost without a mesh.
        tp = config.run.tp_size
        if tp > 1:
            from colearn_federated_learning_tpu.parallel import partition
            self.gather_avoided_bytes = int(partition.estimate_gather_avoided(
                params_np, partition.rules_for_model(config.model.name),
                config.run.tp_axis, tp))
        else:
            self.gather_avoided_bytes = 0

        reg = telemetry.get_registry()
        reg.gauge("fleetsim.devices").set(self.num_devices)
        reg.gauge("fleetsim.chunk_size").set(self.chunk_size)

    # ------------------------------------------------------ constructors --
    @classmethod
    def from_population(
        cls,
        config: ExperimentConfig,
        population,
        traffic,
        cohort_size: int,
        chunk_size: int = 1024,
        fault_plan=None,
        round_deadline_ms: float = 1000.0,
    ) -> "FleetSim":
        """Synthetic fleet: shards materialize on demand from per-device
        keys (fleetsim/population.py); the traffic model picks each
        round's cohort among currently-available devices."""
        from colearn_federated_learning_tpu.models import (
            registry as model_registry,
        )

        spec = population.spec
        model = model_registry.build_model(
            setup_lib.local_model_config(config.model))
        example_x = jnp.asarray(
            population.example_batch(config.fed.batch_size))
        base_key = prng.experiment_key(config.run.seed)
        params = model_registry.init_params(
            model, example_x, prng.init_key(base_key))
        local_update, num_steps = setup_lib.local_trainer_for_config(
            config, model.apply, spec.shard_capacity, lora_dense_ok=True)
        sim = cls(
            config=config,
            local_update=local_update,
            num_steps=num_steps,
            base_key=base_key,
            server_state=strategies.init_server_state(params, config.fed),
            shard_fn=population.materialize,
            budget_fn=lambda ids: population.step_budgets(ids, num_steps),
            select_fn=lambda r: traffic.sample_cohort(r, cohort_size),
            num_devices=spec.num_devices,
            cohort_size=cohort_size,
            chunk_size=chunk_size,
            fault_plan=fault_plan,
            round_deadline_ms=round_deadline_ms,
            available_fraction_fn=lambda r: float(
                traffic.available_mask(r).mean()),
        )
        sim._traffic = traffic
        # Cohort drift attribution needs each device's seeded home class
        # (population.home_classes) — only this constructor has one.
        sim._population = population
        return sim

    @classmethod
    def from_learner(cls, learner, chunk_size: int = 1024,
                     fault_plan=None,
                     round_deadline_ms: float = 1000.0) -> "FleetSim":
        """Wrap a vmap-path :class:`FederatedLearner`: same shards, same
        trainer closure, same base key, the round program's own cohort
        draw — the ONLY difference from ``learner.run_round()`` is the
        chunked dispatch, which is exactly what the parity tests pin
        down."""
        if learner.mesh is not None:
            raise NotImplementedError(
                "from_learner wraps the single-device vmap path; shard "
                "the fleet over a mesh via the engine instead")
        shards = learner.shards
        counts_dev = jnp.asarray(shards.counts)
        num_clients = learner.num_clients
        cohort = learner.cohort_size
        base_key = learner.base_key

        def select(round_idx: int) -> np.ndarray:
            return np.asarray(draw_cohort(
                learner.plan, base_key, jnp.asarray(round_idx, jnp.int32),
                counts_dev)).astype(np.int64)

        def shard_slices(ids: np.ndarray) -> tuple:
            return shards.x[ids], shards.y[ids], shards.counts[ids]

        num_steps = learner.num_steps
        return cls(
            config=learner.config,
            local_update=learner.local_update,
            num_steps=num_steps,
            base_key=base_key,
            server_state=learner.server_state,
            shard_fn=shard_slices,
            budget_fn=lambda ids: np.full(
                ids.shape[0], num_steps, np.int32),
            select_fn=select,
            num_devices=num_clients,
            cohort_size=cohort,
            chunk_size=chunk_size,
            fault_plan=fault_plan,
            round_deadline_ms=round_deadline_ms,
        )

    # -------------------------------------------------- compiled pieces --
    def _build_chunk_fn(self, observe: bool = False, num_classes: int = 1):
        """One chunk's training + weighting, jit-compiled once (static
        chunk shape): vmap(local_update) -> weighted partial sums.  The
        engine's cohort_step semantics, minus the engine-only hooks the
        config validator excluded.

        ``observe=True`` builds the convergence-observatory variant
        (telemetry/convergence.py): same training, plus per-device
        update norms and per-home-class weighted delta sums (``classes``
        carries each device's seeded non-IID cluster) — the raw material
        for cohort drift attribution.  A separate jitted program, so the
        default plane's ``compile_counts`` contract is untouched.
        """
        update = self.local_update
        fed = self.config.fed
        num_steps = self.num_steps

        def core(key, params, x, y, counts, ids, round_idx, budgets,
                 keep):
            # Per-(client, round) keys off the GLOBAL device id:
            # placement/chunking-independent determinism (utils/prng.py).
            keys = jax.vmap(
                lambda i: prng.client_round_key(key, i, round_idx))(ids)
            if fed.straggler_prob > 0.0:
                # The engine's simulated stragglers, same derivation
                # (fed/programs.cohort_step); the fleet's own budget
                # (speed class / delay fault) caps from below.
                skey = prng.straggler_key(key, round_idx)

                def budget_for(i):
                    k = jax.random.fold_in(skey, i)
                    slow = jax.random.bernoulli(k, fed.straggler_prob)
                    frac = jax.random.uniform(jax.random.fold_in(k, 1))
                    return jnp.where(
                        slow, (frac * num_steps).astype(jnp.int32),
                        num_steps)

                budgets = jnp.minimum(budgets, jax.vmap(budget_for)(ids))
            lr_scale = strategies.lr_scale_for_round(fed, round_idx)
            res = jax.vmap(
                update, in_axes=(None, 0, 0, 0, 0, 0, None)
            )(params, x, y, counts, keys, budgets, lr_scale)
            contrib = res.completed & (res.num_examples > 0) & keep
            weights = res.num_examples.astype(jnp.float32) * contrib
            return res, contrib, weights

        def chunk_fn(key, params, x, y, counts, ids, round_idx, budgets,
                     keep):
            res, contrib, weights = core(key, params, x, y, counts, ids,
                                         round_idx, budgets, keep)
            wsum = pytrees.tree_weighted_sum(res.delta, weights)
            total_w = jnp.sum(weights)
            loss_sum = jnp.sum(res.mean_loss * weights)
            n_comp = jnp.sum(contrib.astype(jnp.int32))
            return wsum, total_w, loss_sum, n_comp

        if not observe:
            return jax.jit(chunk_fn)

        def obs_chunk_fn(key, params, x, y, counts, ids, round_idx,
                         budgets, keep, classes):
            res, contrib, weights = core(key, params, x, y, counts, ids,
                                         round_idx, budgets, keep)
            wsum = pytrees.tree_weighted_sum(res.delta, weights)
            total_w = jnp.sum(weights)
            loss_sum = jnp.sum(res.mean_loss * weights)
            n_comp = jnp.sum(contrib.astype(jnp.int32))
            # Per-device update norm, zeroed for non-contributors (and
            # for padding lanes, whose keep mask is False).
            sq = sum(jnp.sum(jnp.square(leaf),
                             axis=tuple(range(1, leaf.ndim)))
                     for leaf in jax.tree.leaves(res.delta))
            dev_norms = jnp.sqrt(sq) * contrib
            # Per-home-class weighted delta sums: the cohort-attribution
            # numerators (num_classes is static — one extra signature).
            class_w = jax.ops.segment_sum(weights, classes, num_classes)
            class_wsum = jax.tree.map(
                lambda leaf: jax.ops.segment_sum(
                    leaf * weights.reshape((-1,) + (1,) * (leaf.ndim - 1)),
                    classes, num_classes),
                res.delta)
            return ((wsum, total_w, loss_sum, n_comp),
                    dev_norms, (class_wsum, class_w))

        return jax.jit(obs_chunk_fn)

    def _build_finish_fn(self):
        """The engine's round epilogue (fed/programs.finish_round, plain
        path): zero-contributor rounds are a no-op server update."""
        fed = self.config.fed

        def finish(server_state, wsum, total_w, loss_sum, n_comp):
            denom = jnp.where(total_w > 0, total_w, 1.0)
            mean_delta = pytrees.tree_scale(
                wsum, jnp.where(total_w > 0, 1.0 / denom, 0.0))
            new_state = strategies.server_update(server_state, mean_delta,
                                                 fed)
            metrics = {
                "train_loss": loss_sum / denom,
                "completed": n_comp,
                "total_weight": total_w,
            }
            # mean_delta rides along for the convergence observatory —
            # already materialized, so exposing it costs nothing on the
            # default plane (it is simply never fetched).
            return new_state, mean_delta, metrics

        return jax.jit(finish)

    def _zero_acc(self):
        wsum = jax.tree.map(
            lambda l: jnp.zeros(l.shape, jnp.float32),
            self.server_state.params)
        return (wsum, jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))

    # ------------------------------------------------------------ faults --
    def _resolve_faults(self, ids: np.ndarray, round_idx: int):
        """Host-side fault resolution for the round cohort: one
        ``plan.match`` per cohort device on the ``(device, round,
        op="train")`` key — the same key space the transport injector
        consumes (faults/inject.py), so one plan drives every plane.
        Returns ``(keep_weight, budget_scale_ms, uplink_ok, stats)``."""
        n = ids.shape[0]
        keep = np.ones(n, bool)          # contributes to the aggregate
        uplink = np.ones(n, bool)        # spends uplink bytes
        trains = np.ones(n, bool)        # runs local training at all
        lost_ms = np.zeros(n, np.float64)
        plan = self.fault_plan
        if plan is None:
            from colearn_federated_learning_tpu.faults import inject

            plan = inject.active_plan()
        stats = {"dropped": 0, "straggled": 0, "corrupted": 0}
        if plan is None:
            return keep, trains, uplink, lost_ms, stats
        for j in range(n):
            did = str(int(ids[j]))
            fired = plan.match(did, round_idx, "train",
                               kinds=_FLEET_FAULT_KINDS, site="server")
            for f in fired:
                _count_fault(f.kind)
                if f.kind == "drop_request":
                    keep[j] = uplink[j] = trains[j] = False
                    stats["dropped"] += 1
                    if self.health is not None:
                        self.health.record(did, round=round_idx,
                                           deadline_miss=1)
                elif f.kind == "delay":
                    lost_ms[j] += f.ms
                    stats["straggled"] += 1
                    if self.health is not None:
                        # The injected delay IS this device's observed
                        # extra latency in the simulated plane.
                        self.health.record(did, round=round_idx,
                                           latency_s=f.ms / 1000.0)
                elif f.kind == "corrupt_payload":
                    keep[j] = False
                    stats["corrupted"] += 1
                    if self.health is not None:
                        self.health.record(did, round=round_idx,
                                           corrupt_frame=1)
        return keep, trains, uplink, lost_ms, stats

    # ------------------------------------------------------------- round --
    def run_round(self) -> dict:
        """One simulated federated round over a traffic-sampled cohort."""
        r = len(self.history)
        t0 = time.perf_counter()
        reg = telemetry.get_registry()
        with self.tracer.span("fleet_round", round=r):
            with self.tracer.span("cohort_sample", round=r):
                ids = np.asarray(self._select_fn(r), np.int64)
            keep_w, trains, uplink, lost_ms, fstats = self._resolve_faults(
                ids, r)
            budgets = self._budget_fn(ids).astype(np.int32)
            if np.any(lost_ms > 0):
                frac = np.clip(1.0 - lost_ms / self.round_deadline_ms,
                               0.0, 1.0)
                budgets = np.minimum(
                    budgets, np.floor(frac * self.num_steps)).astype(
                        np.int32)
            # Dropped devices never train: zero budget AND zero weight
            # (the masked scan still runs their lane — shapes are
            # static — but no step executes and nothing aggregates).
            budgets = np.where(trains, budgets, 0)

            n = ids.shape[0]
            chunk = self.chunk_size
            padded = max(chunk, ((n + chunk - 1) // chunk) * chunk)
            ids_pad = np.zeros(padded, np.int64)
            ids_pad[:n] = ids
            keep_pad = np.zeros(padded, bool)
            keep_pad[:n] = keep_w
            bud_pad = np.zeros(padded, np.int32)
            bud_pad[:n] = budgets

            params = self.server_state.params
            acc = self._zero_acc()
            r_dev = jnp.asarray(r, jnp.int32)
            observing = self._learn is not None
            if observing:
                cls_pad = np.zeros(padded, np.int32)
                if self._population is not None:
                    cls_pad[:n] = self._population.home_classes(ids)
                dev_norm_parts: list = []
                class_acc = None
            with self.tracer.span("train_chunks", round=r, cohort=n,
                                  chunks=padded // chunk):
                if n:
                    for lo in range(0, padded, chunk):  # colearn: hot
                        # Child span per chunk: trace-summary renders the
                        # sweep's phase mix instead of one opaque block
                        # (recording is gated on tracer.enabled; timing
                        # costs two clock reads).
                        with self.tracer.span("train_chunk", round=r,
                                              chunk=lo // chunk):
                            sl = slice(lo, lo + chunk)
                            cx, cy, cc = self._shard_fn(ids_pad[sl])
                            if observing:
                                part, dn, cpart = self._obs_program()(
                                    self.base_key, params, cx, cy, cc,
                                    ids_pad[sl], r_dev, bud_pad[sl],
                                    keep_pad[sl], cls_pad[sl])
                                dev_norm_parts.append(dn)
                                class_acc = (cpart if class_acc is None
                                             else jax.tree.map(
                                                 jnp.add, class_acc, cpart))
                            else:
                                part = self._chunk_fn(
                                    self.base_key, params, cx, cy, cc,
                                    ids_pad[sl], r_dev, bud_pad[sl],
                                    keep_pad[sl])
                            acc = self._fold_fn(acc, part)
            with self.tracer.span("server_update", round=r) as up_sp:
                self.server_state, mean_delta, metrics = self._finish_fn(
                    self.server_state, *acc)
                out = {k: float(v)
                       for k, v in jax.device_get(metrics).items()}
                conv_sig = None
                if observing:
                    conv_sig = self._learn_round_feed(
                        r, ids, mean_delta, up_sp,
                        dev_norm_parts if n else [],
                        class_acc)

        n_trained = int(trains.sum())
        n_reporting = int(uplink.sum())
        bytes_down = n_trained * self.down_frame_bytes
        bytes_up = n_reporting * self.up_frame_bytes
        out.update(
            round=r,
            cohort=n,
            cohort_requested=self.cohort_size,
            clients_trained=n_trained,
            bytes_down_est=bytes_down,
            bytes_up_est=bytes_up,
            **fstats,
        )
        if conv_sig:
            # conv_* learning-health keys only under --learn-observe —
            # default round records stay byte-identical (pinned by test).
            out.update(conv_sig)
        if self.gather_avoided_bytes:
            # Key present only under a sharded server (tp_size > 1), so
            # default round records stay byte-identical.  One broadcast
            # encode per round → one per-encode avoidance charge.
            out["bytes_gather_avoided_est"] = self.gather_avoided_bytes
            reg.counter("fleetsim.bytes_gather_avoided_est_total").inc(
                self.gather_avoided_bytes)
        if self.up_saved_bytes:
            # Uplink codec on (fed.compress != "none"): same conditional-
            # key convention as above.
            bytes_up_saved = n_reporting * self.up_saved_bytes
            out["bytes_up_saved_est"] = bytes_up_saved
            reg.counter("fleetsim.bytes_up_saved_est_total").inc(
                bytes_up_saved)
        if self._available_fraction_fn is not None:
            frac = self._available_fraction_fn(r)
            out["available_fraction"] = frac
            reg.gauge("fleetsim.available_fraction").set(frac)
        out["round_time_s"] = time.perf_counter() - t0
        if self.health is not None:
            # Durable once per round; health_* keys only when the plane
            # is on (default records stay byte-identical).
            self.health.flush()
            out.update(telemetry.health_record_keys(self.health.devices()))
        reg.counter("fleetsim.rounds_total").inc()
        reg.counter("fleetsim.clients_trained_total").inc(n_trained)
        reg.counter("fleetsim.bytes_down_est_total").inc(bytes_down)
        reg.counter("fleetsim.bytes_up_est_total").inc(bytes_up)
        reg.histogram("fleetsim.round_time_s").observe(out["round_time_s"])
        self.history.append(out)
        return out

    def _obs_program(self):
        """Lazily-built observatory chunk program: it needs the
        population's ``num_classes`` (from_learner planes lack one and
        fall back to a single bucket), and building it only on first use
        keeps the default plane's program set untouched."""
        if self._obs_chunk_fn is None:
            ncls = (self._population.spec.num_classes
                    if self._population is not None else 1)
            self._obs_chunk_fn = telemetry.CompileTracker(
                self._build_chunk_fn(observe=True, num_classes=ncls),
                name="fleetsim.obs_chunk")
        return self._obs_chunk_fn

    def _learn_round_feed(self, r: int, ids: np.ndarray, mean_delta,
                          span, dev_norm_parts: list, class_acc):
        """Fold the round's learning signals: aggregate norm/cos/trend
        from the observatory, per-device skew (anomalous norms feed the
        health ledger — a diverging device is a health event, same as a
        straggler), per-cohort drift attribution, span attrs, and the
        learn.* metric export.  Returns the record's conv_* dict."""
        from colearn_federated_learning_tpu.telemetry import convergence

        sig = self._learn.observe(mean_delta,
                                  lr=self.config.fed.server_lr)
        if sig is None:
            return None
        n = ids.shape[0]
        if dev_norm_parts:
            norms = np.concatenate(
                [np.asarray(p) for p in dev_norm_parts])[:n]
            contributors = norms > 0.0
            if contributors.any():
                sk = convergence.device_skew(norms[contributors])
                sig["conv_norm_median"] = round(sk["median"], 8)
                sig["conv_norm_p90"] = round(sk["p90"], 8)
                sig["conv_norm_anomalies"] = len(sk["anomalies"])
                if self.health is not None and sk["anomalies"]:
                    cids = ids[contributors]
                    for idx in sk["anomalies"]:
                        self.health.record(str(int(cids[idx])), round=r,
                                           norm_anomaly=1)
        if class_acc is not None and self._population is not None:
            class_wsum, class_w = class_acc
            sig.update(convergence.cohort_skew(
                class_wsum, np.asarray(class_w), mean_delta))
        span.attrs["conv_update_norm"] = sig["conv_update_norm"]
        span.attrs["conv_trend"] = sig["conv_trend"]
        if "conv_cos_prev" in sig:
            span.attrs["conv_cos_prev"] = sig["conv_cos_prev"]
        self._learn.export_metrics(telemetry.get_registry(), sig)
        return sig

    @property
    def compile_counts(self) -> dict:
        """Distinct XLA signatures per jitted program.  The chunked-vmap
        invariant — zero-padding makes every chunk the same shape — holds
        exactly when ``chunk`` stays at 1 across a whole sweep."""
        out = {
            "chunk": self._chunk_fn.compiles,
            "finish": self._finish_fn.compiles,
            "fold": self._fold_fn.compiles,
        }
        if self._obs_chunk_fn is not None:
            # Observatory program, present only under --learn-observe —
            # the default trio above is contract-pinned.
            out["obs_chunk"] = self._obs_chunk_fn.compiles
        return out

    def fit(self, rounds: int, log_fn=None) -> list[dict]:
        for _ in range(rounds):
            rec = self.run_round()
            if log_fn is not None:
                log_fn(rec)
        return self.history

    # ------------------------------------------------------------- async --
    def _async_arrival_wait(self, rng, ids: np.ndarray,
                            now_min: float) -> np.ndarray:
        """Minutes until each device's NEXT check-in, drawn from the
        diurnal-Poisson traffic model at sim time ``now_min``: the
        per-device arrival rate is recovered from the model's window
        probability (p = 1 - exp(-rate * window)), so the async plane
        consumes the exact rates the sync cohort sampler does."""
        spec = self._traffic.spec
        rnd = int(now_min / spec.round_minutes)
        p = np.clip(self._traffic.availability_probability(rnd, ids),
                    1e-6, 1.0 - 1e-9)
        rate_per_min = -np.log1p(-p) / spec.round_minutes
        return rng.exponential(1.0, size=ids.shape[0]) / rate_per_min

    def fit_async(
        self,
        aggregations: int,
        buffer_size=32,
        *,
        staleness_exponent: float = 0.5,
        max_staleness: int = 10,
        prune_after: int = 0,
        probation: int = 8,
        straggler_fraction: float = 0.05,
        straggler_multiplier: float = 20.0,
        observe: bool = False,
        auto_interval_min: Optional[float] = None,
        aggregators: int = 0,
        log_fn=None,
    ) -> list[dict]:
        """Buffered-asynchronous simulation (FedBuff semantics over the
        chunked-vmap hot path): devices check in on the diurnal-Poisson
        traffic model, train against the model version current at
        dispatch, and the server folds every ``buffer_size`` completions
        with staleness weights ``(1 + tau)^(-staleness_exponent)``,
        discarding updates staler than ``max_staleness``.

        The event clock is virtual (sim minutes): per-device service
        time is lognormal around the traffic model's round window, with
        a seeded ``straggler_fraction`` of chronic stragglers at
        ``straggler_multiplier`` x — the tail that bounds a SYNC round
        but not async throughput, which tracks the arrival rate
        (``arrival_rate_per_min`` vs ``agg_rate_per_min`` in the
        records; scripts/bench_fleet.py --async-sweep scales the same
        model analytically to 1M devices).

        ``prune_after`` > 0 arms the coordinator's straggler-pruning
        policy in the sim: a device whose updates are discarded
        too-stale ``prune_after`` times consecutively stops being
        re-dispatched for ``probation`` aggregations — pruned runs must
        waste measurably fewer updates at equal final loss (the
        ``fleet_async_prune`` bench gate).  Groups the buffer by
        dispatch version and reuses the round-path chunk/fold/finish
        programs, so the compile-once invariant holds (chunk shapes stay
        ``chunk_size``-padded).

        ``buffer_size="auto"`` sizes K from the seeded-EWMA arrival-rate
        estimator before every aggregation (K = observed rate × fold
        fraction × ``auto_interval_min``, the target fold cadence;
        default ``round_minutes``; resizes slew-limited to ±50%) — the
        diurnal traffic model makes the rate swing, and auto-K keeps the
        fold cadence in band instead of letting a fixed K's cadence
        (and the stragglers' realized τ) swing with it.  ``observe`` stamps observatory keys (staleness
        tail, contribution mass, EWMA arrival rate) into records;
        implied by auto-K, off by default so default async records stay
        byte-identical.

        ``aggregators`` > 0 switches to the TWO-TIER tree-async plane
        (:meth:`_fit_async_tree`): per-slice buffers with their own
        auto-K, partials folded unscaled at the edge and staleness-
        discounted at the root against the partial's OLDEST constituent
        version.  Default (0) records stay byte-identical."""
        import heapq

        if aggregators:
            return self._fit_async_tree(
                aggregations, aggregators, buffer_size,
                staleness_exponent=staleness_exponent,
                max_staleness=max_staleness, prune_after=prune_after,
                probation=probation,
                straggler_fraction=straggler_fraction,
                straggler_multiplier=straggler_multiplier,
                observe=observe, auto_interval_min=auto_interval_min,
                log_fn=log_fn)
        if self._traffic is None:
            raise NotImplementedError(
                "fit_async needs the traffic model: build the sim with "
                "FleetSim.from_population")
        n_dev = self.num_devices
        auto_buffer = isinstance(buffer_size, str)
        if auto_buffer:
            if buffer_size != "auto":
                raise ValueError(
                    f"buffer_size must be an int >= 1 or 'auto', "
                    f"got {buffer_size!r}")
            buffer_size = min(8, n_dev)   # warm-start K
        elif buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")
        if buffer_size > n_dev:
            raise ValueError(
                f"buffer_size {buffer_size} exceeds the {n_dev}-device "
                "fleet — the buffer could never fill")
        if buffer_size > self.chunk_size:
            raise ValueError(
                f"buffer_size {buffer_size} exceeds chunk_size "
                f"{self.chunk_size} — the version-grouped fold pads "
                "each group to one compiled chunk dispatch")
        observe = bool(observe) or auto_buffer
        spec = self._traffic.spec
        if auto_interval_min is None:
            auto_interval_min = spec.round_minutes
        # Arrival-rate estimator on the VIRTUAL clock (sim minutes) —
        # rates come out per sim-minute, the same unit as the records.
        est = telemetry.ArrivalEstimator()
        rng = np.random.default_rng(
            np.random.SeedSequence([self.config.run.seed, 0xA51C]))
        # Per-device service time (sim minutes): lognormal around the
        # traffic window, chronic stragglers seeded at the head of a
        # permutation so the set is deterministic per (seed, fleet).
        service = spec.round_minutes * rng.lognormal(
            0.0, 0.5, size=n_dev)
        n_slow = int(round(straggler_fraction * n_dev))
        slow_ids = rng.permutation(n_dev)[:n_slow]
        service[slow_ids] *= straggler_multiplier
        reg = telemetry.get_registry()
        reg.gauge("fleetsim.async_buffer_size").set(buffer_size)

        version = 0
        ring: dict[int, object] = {0: self.server_state.params}
        heap: list = []          # (t_done, seq, device_id, version)
        seq = 0
        all_ids = np.arange(n_dev, dtype=np.int64)
        wait0 = self._async_arrival_wait(rng, all_ids, 0.0)
        for d in range(n_dev):
            heapq.heappush(heap, (wait0[d] + service[d], seq, d, 0))
            seq += 1
        now = 0.0
        arrivals = 0
        wasted = 0
        stale_streak: dict[int, int] = {}
        pruned: dict[int, int] = {}   # device -> aggregation to re-admit
        pruned_total = 0
        base_len = len(self.history)
        start = time.perf_counter()

        def redispatch(d: int, t: float) -> None:
            nonlocal seq
            wait = float(self._async_arrival_wait(
                rng, np.asarray([d], np.int64), t)[0])
            heapq.heappush(heap, (t + wait + service[d], seq, d, version))
            seq += 1

        for agg in range(aggregations):
            t0 = time.perf_counter()
            # Probation re-admission first: a re-admitted device rejoins
            # the arrival stream at the current version, clean streak.
            for d in [d for d, until in pruned.items() if until <= agg]:
                del pruned[d]
                stale_streak.pop(d, None)
                redispatch(d, now)
            if auto_buffer:
                # Retune K to the observed arrival rate: one fold per
                # auto_interval_min, clamped to the active (un-pruned)
                # fleet — only that many updates can be in flight while
                # the buffer fills.  Only FOLDED arrivals fill the
                # buffer, so the target interval is scaled by the
                # observed fold fraction — sizing K off raw arrivals
                # overshoots exactly when staleness discards bite, and
                # the realized cadence drifts out of the band.
                # K is also clamped to chunk_size: the version-grouped
                # fold pads each group to ONE compiled chunk dispatch,
                # so a buffer wider than the chunk could overflow a
                # group.
                fold_frac = 1.0 - wasted / arrivals if arrivals else 1.0
                k = est.recommend_buffer(
                    auto_interval_min * max(fold_frac, 0.05), lo=1,
                    hi=max(1, min(self.chunk_size, n_dev - len(pruned))),
                    current=buffer_size)
                # Slew-limit the resize: the rate estimate trails the
                # diurnal swing by one fill, so jumping straight to the
                # recommendation overshoots the band it is chasing.
                k = int(np.clip(k, max(1, buffer_size // 2),
                                max(2, buffer_size * 3 // 2)))
                if k != buffer_size:
                    reg.counter(
                        "fleetsim.async_buffer_resizes_total").inc()
                    buffer_size = k
                reg.gauge("fleetsim.async_buffer_size").set(buffer_size)
            buffered: list[tuple[int, int]] = []   # (device, version)
            discarded = 0
            mass_folded = 0.0
            mass_discarded = 0.0
            while len(buffered) < buffer_size:
                t_done, _, d, v = heapq.heappop(heap)
                now = max(now, t_done)
                arrivals += 1
                est.observe(str(d), now=now)
                tau = version - v
                if tau > max_staleness:
                    # Too stale: wasted compute + uplink.  The chronic
                    # stragglers this counts are what pruning exists to
                    # stop paying for.
                    discarded += 1
                    wasted += 1
                    s_w = float((1.0 + tau) ** -staleness_exponent)
                    mass_discarded += s_w
                    reg.counter(
                        "fleetsim.async_contribution_mass",
                        labels={"outcome": "discarded"}).inc(s_w)
                    reg.histogram(
                        "fleetsim.async_staleness",
                        labels={"outcome": "discarded"}).observe(
                            float(tau))
                    reg.counter(
                        "fleetsim.async_updates_discarded_total").inc()
                    streak = stale_streak.get(d, 0) + 1
                    stale_streak[d] = streak
                    if (prune_after > 0 and streak >= prune_after
                            and n_dev - len(pruned) - 1 >= buffer_size):
                        pruned[d] = agg + probation
                        pruned_total += 1
                        reg.counter(
                            "fleetsim.async_devices_pruned_total").inc()
                    else:
                        redispatch(d, now)
                    continue
                stale_streak.pop(d, None)
                s_w = float((1.0 + tau) ** -staleness_exponent)
                mass_folded += s_w
                reg.counter("fleetsim.async_contribution_mass",
                            labels={"outcome": "folded"}).inc(s_w)
                reg.histogram("fleetsim.async_staleness",
                              labels={"outcome": "folded"}).observe(
                                  float(tau))
                buffered.append((d, v))

            # Fold the buffer grouped by dispatch version: every update
            # in a group trained against the same ring snapshot, so one
            # chunk dispatch per group reuses the compiled round
            # programs on chunk_size-padded shapes.
            acc = self._zero_acc()
            stalenesses = [version - v for _, v in buffered]
            for v in sorted({v for _, v in buffered}):
                ids = np.asarray([d for d, dv in buffered if dv == v],
                                 np.int64)
                s_w = float((1.0 + (version - v)) ** -staleness_exponent)
                padded = np.zeros(self.chunk_size, np.int64)
                padded[:ids.shape[0]] = ids
                keep = np.zeros(self.chunk_size, bool)
                keep[:ids.shape[0]] = True
                budgets = np.zeros(self.chunk_size, np.int32)
                budgets[:ids.shape[0]] = self._budget_fn(ids).astype(
                    np.int32)
                cx, cy, cc = self._shard_fn(padded)
                part = self._chunk_fn(
                    self.base_key, ring[v], cx, cy, cc, padded,
                    jnp.asarray(v, jnp.int32), budgets, keep)
                wsum, total_w, loss_sum, n_comp = part
                part = (pytrees.tree_scale(wsum, s_w), total_w * s_w,
                        loss_sum * s_w, n_comp)
                acc = self._fold_fn(acc, part)
            self.server_state, mean_delta, metrics = self._finish_fn(
                self.server_state, *acc)
            out = {k: float(v) for k, v in jax.device_get(metrics).items()}
            conv_sig = None
            if self._learn is not None:
                conv_sig = self._learn.observe(
                    mean_delta, lr=self.config.fed.server_lr)
                if conv_sig:
                    self._learn.export_metrics(reg, conv_sig)
            version += 1
            ring[version] = self.server_state.params
            for v in [v for v in ring if v < version - max_staleness]:
                del ring[v]
            for d, _ in buffered:
                redispatch(d, now)

            rec = {
                "aggregation": base_len + agg,
                "model_version": version,
                "buffer_size": buffer_size,
                "staleness_mean": float(np.mean(stalenesses)),
                "staleness_max": int(np.max(stalenesses)),
                "discarded": discarded,
                "contributors": len(buffered),
                "train_loss": out["train_loss"],
                "total_weight": out["total_weight"],
                "sim_time_min": now,
                "arrival_rate_per_min": arrivals / max(now, 1e-9),
                "agg_rate_per_min": (agg + 1) / max(now, 1e-9),
                "wasted_updates_total": wasted,
                "agg_time_s": time.perf_counter() - t0,
            }
            reg.gauge("fleetsim.async_arrival_rate_per_min").set(
                est.rate())
            if observe:
                # Observatory keys — only when observe/auto-K is on, so
                # default async records stay byte-identical.
                rec["arrival_rate_ewma_per_min"] = round(est.rate(), 6)
                rec["mass_folded"] = round(mass_folded, 6)
                rec["mass_discarded"] = round(mass_discarded, 6)
                hs = reg.histogram(
                    "fleetsim.async_staleness",
                    labels={"outcome": "folded"}).summary()
                if hs.get("count"):
                    rec["staleness_p50"] = hs["p50"]
                    rec["staleness_p90"] = hs["p90"]
                    rec["staleness_p99"] = hs["p99"]
            if prune_after > 0:
                # Conditional keys, same convention as the socket plane:
                # default async records stay byte-identical with the
                # feature off.
                rec["pruned"] = len(pruned)
                rec["pruned_total"] = pruned_total
            if conv_sig:
                # conv_* learning-health keys only under --learn-observe.
                rec.update(conv_sig)
            reg.counter("fleetsim.async_aggregations_total").inc()
            self.history.append(rec)
            if log_fn is not None:
                log_fn(rec)
        reg.gauge("fleetsim.async_sim_minutes").set(now)
        reg.histogram("fleetsim.round_time_s").observe(
            time.perf_counter() - start)
        return self.history

    def _fit_async_tree(
        self,
        aggregations: int,
        aggregators: int,
        buffer_size,
        *,
        staleness_exponent: float,
        max_staleness: int,
        prune_after: int,
        probation: int,
        straggler_fraction: float,
        straggler_multiplier: float,
        observe: bool,
        auto_interval_min: Optional[float],
        log_fn,
    ) -> list[dict]:
        """Two-tier buffered-async: per-slice aggregator buffers over the
        same virtual event clock as :meth:`fit_async`.

        Devices are sliced across ``aggregators`` by SERVICE TIME
        (sorted, contiguous divmod) — the health-driven assignment the
        socket plane computes from ledger scores, which concentrates
        chronic stragglers in the last slice so their deep buffer
        absorbs the tail instead of every buffer carrying a piece of it.
        Each slice runs its own :class:`~.telemetry.ArrivalEstimator`
        and auto-K buffer (slew-limited to ±50% per retune, the same
        band as the flat auto-K): one partial per ``auto_interval_min``
        of that slice's measured arrival rate.

        A full slice buffer ships a PARTIAL: its version groups fold
        UNSCALED at the edge (the aggregator cannot know the root's
        version when contributions keep arriving), and the root scales
        the whole partial by ``(1 + tau)^-exp`` where ``tau`` is
        measured against the partial's OLDEST constituent version —
        exactly the socket tree-async plane's semantics.  A partial
        whose oldest constituent exceeds ``max_staleness`` is discarded
        WHOLE (``fleetsim.async_partials_discarded_total``); one root
        aggregation applies one surviving partial.

        Per-slice fold-cadence tracking: ``agg_fold_tracking_min`` is
        the worst slice's ``min(r, 1/r)`` for ``r = realized mean ship
        interval / target interval`` — 1.0 when every buffer folds on
        cadence, sagging toward 0 when a slice folds far too rarely
        (starved) OR far too often (K undersized).  The
        ``fleet_tree_async`` bench sentinel holds the floor."""
        import heapq

        if self._traffic is None:
            raise NotImplementedError(
                "fit_async needs the traffic model: build the sim with "
                "FleetSim.from_population")
        n_dev = self.num_devices
        if aggregators < 2:
            raise ValueError(
                f"tree-async needs >= 2 aggregators, got {aggregators}")
        if aggregators > n_dev:
            raise ValueError(
                f"{aggregators} aggregators exceed the {n_dev}-device "
                "fleet — a slice would be empty")
        warm = 8 if isinstance(buffer_size, str) else int(buffer_size)
        if not isinstance(buffer_size, str) and buffer_size < 1:
            raise ValueError(f"buffer_size must be >= 1, got {buffer_size}")
        observe = True      # tree mode is always auto-K (implies observe)
        spec = self._traffic.spec
        if auto_interval_min is None:
            auto_interval_min = spec.round_minutes
        rng = np.random.default_rng(
            np.random.SeedSequence([self.config.run.seed, 0xA51C]))
        service = spec.round_minutes * rng.lognormal(
            0.0, 0.5, size=n_dev)
        n_slow = int(round(straggler_fraction * n_dev))
        slow_ids = rng.permutation(n_dev)[:n_slow]
        service[slow_ids] *= straggler_multiplier
        reg = telemetry.get_registry()

        # Service-time-sorted contiguous slices: slice 0 gets the fast
        # devices, the last slice the stragglers (deep buffer).
        order = np.argsort(service, kind="stable")
        base, extra = divmod(n_dev, aggregators)
        slice_of = np.empty(n_dev, np.int64)
        slice_ids: list[np.ndarray] = []
        pos = 0
        for a in range(aggregators):
            size = base + (1 if a < extra else 0)
            members = order[pos:pos + size]
            slice_of[members] = a
            slice_ids.append(members)
            pos += size

        ests = [telemetry.ArrivalEstimator() for _ in range(aggregators)]
        ks = [max(1, min(warm, len(slice_ids[a]), self.chunk_size))
              for a in range(aggregators)]
        buffers: list[list[tuple[int, int]]] = [[] for _ in
                                                range(aggregators)]
        ship_times: list[list[float]] = [[] for _ in range(aggregators)]
        partials_folded = [0] * aggregators

        version = 0
        ring: dict[int, object] = {0: self.server_state.params}
        heap: list = []          # (t_done, seq, device_id, version)
        seq = 0
        all_ids = np.arange(n_dev, dtype=np.int64)
        wait0 = self._async_arrival_wait(rng, all_ids, 0.0)
        for d in range(n_dev):
            heapq.heappush(heap, (wait0[d] + service[d], seq, d, 0))
            seq += 1
        now = 0.0
        arrivals = 0
        wasted = 0
        stale_streak: dict[int, int] = {}
        pruned: dict[int, int] = {}   # device -> aggregation to re-admit
        pruned_total = 0
        base_len = len(self.history)
        start = time.perf_counter()

        def redispatch(d: int, t: float) -> None:
            nonlocal seq
            wait = float(self._async_arrival_wait(
                rng, np.asarray([d], np.int64), t)[0])
            heapq.heappush(heap, (t + wait + service[d], seq, d, version))
            seq += 1

        def retune(a: int) -> None:
            # Auto-K on the slice's OWN arrival rate, slew-limited so
            # the resize trails the diurnal swing instead of chasing it.
            cur = ks[a]
            active = sum(1 for d in slice_ids[a] if int(d) not in pruned)
            hi = max(1, min(self.chunk_size, active))
            k = ests[a].recommend_buffer(auto_interval_min, lo=1, hi=hi,
                                         current=cur)
            k = int(np.clip(k, max(1, cur // 2), max(2, cur * 3 // 2)))
            k = max(1, min(k, hi))
            if k != cur:
                reg.counter("fleetsim.async_buffer_resizes_total").inc()
            ks[a] = k

        def tracking_min() -> float:
            # Per-slice cadence tracking: realized mean ship interval vs
            # the interval auto-K can actually DELIVER for this slice —
            # the target clipped into the achievable band [1/rate,
            # hi/rate] (K is an integer in [1, hi]; a slice whose
            # arrival rate over- or under-shoots the band is capacity-
            # limited, not mistracking).  Trailing window (last 5
            # intervals) so the warm-start transient ages out; ``min(r,
            # 1/r)`` sags on a buffer folding far off its own band —
            # starved, stuck, or thrashing — which is what the
            # ``fleet_tree_async`` sentinel floors.
            vals = []
            for a in range(aggregators):
                rate = ests[a].rate()
                active = sum(1 for d in slice_ids[a]
                             if int(d) not in pruned)
                hi = max(1, min(self.chunk_size, active))
                t_eff = auto_interval_min
                if rate > 0:
                    t_eff = float(np.clip(auto_interval_min,
                                          1.0 / rate, hi / rate))
                ts = ship_times[a][-6:]
                if len(ts) >= 2:
                    realized = (ts[-1] - ts[0]) / (len(ts) - 1)
                    r = realized / max(t_eff, 1e-9)
                    vals.append(min(r, 1.0 / r) if r > 0 else 0.0)
                elif len(ts) == 1:
                    vals.append(1.0)   # one ship — no interval yet
                else:
                    # Never shipped: on cadence only while younger than
                    # two achievable intervals.
                    vals.append(1.0 if now <= 2 * t_eff else 0.0)
            return round(min(vals), 6)

        for agg in range(aggregations):
            t0 = time.perf_counter()
            for d in [d for d, until in pruned.items() if until <= agg]:
                del pruned[d]
                stale_streak.pop(d, None)
                redispatch(d, now)
            discarded_partials = 0
            mass_folded = 0.0
            mass_discarded = 0.0
            while True:
                # Pump arrivals into slice buffers until one fills.
                while True:
                    t_done, _, d, v = heapq.heappop(heap)
                    now = max(now, t_done)
                    arrivals += 1
                    a = int(slice_of[d])
                    ests[a].observe(str(d), now=now)
                    buffers[a].append((int(d), int(v)))
                    if len(buffers[a]) >= ks[a]:
                        break
                batch, buffers[a] = buffers[a], []
                k_ship = ks[a]
                ship_times[a].append(now)
                retune(a)
                oldest = min(v for _, v in batch)
                tau = version - oldest
                s_w = float((1.0 + tau) ** -staleness_exponent)
                if tau > max_staleness:
                    # Whole-partial discard: the root cannot unpick one
                    # constituent out of a pre-folded sum.
                    discarded_partials += 1
                    wasted += len(batch)
                    reg.counter(
                        "fleetsim.async_partials_discarded_total").inc()
                    for dd, dv in batch:
                        dtau = version - dv
                        dw = float((1.0 + dtau) ** -staleness_exponent)
                        mass_discarded += dw
                        reg.counter(
                            "fleetsim.async_contribution_mass",
                            labels={"outcome": "discarded"}).inc(dw)
                        reg.histogram(
                            "fleetsim.async_staleness",
                            labels={"outcome": "discarded"}).observe(
                                float(dtau))
                        reg.counter(
                            "fleetsim.async_updates_discarded_total").inc()
                        # Prune streaks accrue only to devices whose OWN
                        # contribution was too stale — fresh constituents
                        # batched with a stale one are collateral of the
                        # whole-partial discard, not stragglers.
                        if dtau > max_staleness:
                            streak = stale_streak.get(dd, 0) + 1
                            stale_streak[dd] = streak
                        else:
                            streak = 0
                        active = sum(1 for x in slice_ids[a]
                                     if int(x) not in pruned)
                        if (prune_after > 0 and streak >= prune_after
                                and active > 1):
                            pruned[dd] = agg + probation
                            pruned_total += 1
                            reg.counter(
                                "fleetsim.async_devices_pruned_total"
                            ).inc()
                        else:
                            redispatch(dd, now)
                    continue
                break

            # Fold the partial: version groups UNSCALED at the edge,
            # then one root-side staleness discount for the whole
            # partial keyed off its oldest constituent.
            stalenesses = [version - v for _, v in batch]
            acc = self._zero_acc()
            for v in sorted({v for _, v in batch}):
                ids = np.asarray([dd for dd, dv in batch if dv == v],
                                 np.int64)
                padded = np.zeros(self.chunk_size, np.int64)
                padded[:ids.shape[0]] = ids
                keep = np.zeros(self.chunk_size, bool)
                keep[:ids.shape[0]] = True
                budgets = np.zeros(self.chunk_size, np.int32)
                budgets[:ids.shape[0]] = self._budget_fn(ids).astype(
                    np.int32)
                cx, cy, cc = self._shard_fn(padded)
                part = self._chunk_fn(
                    self.base_key, ring[v], cx, cy, cc, padded,
                    jnp.asarray(v, jnp.int32), budgets, keep)
                acc = self._fold_fn(acc, part)
            wsum, total_w, loss_sum, n_comp = acc
            acc = (pytrees.tree_scale(wsum, s_w), total_w * s_w,
                   loss_sum * s_w, n_comp)
            self.server_state, mean_delta, metrics = self._finish_fn(
                self.server_state, *acc)
            out = {k: float(x) for k, x in jax.device_get(metrics).items()}
            conv_sig = None
            if self._learn is not None:
                conv_sig = self._learn.observe(
                    mean_delta, lr=self.config.fed.server_lr)
                if conv_sig:
                    self._learn.export_metrics(reg, conv_sig)
            for dd, dv in batch:
                stale_streak.pop(dd, None)
                dtau = version - dv
                dw = float((1.0 + dtau) ** -staleness_exponent)
                mass_folded += dw
                reg.counter("fleetsim.async_contribution_mass",
                            labels={"outcome": "folded"}).inc(dw)
                reg.histogram("fleetsim.async_staleness",
                              labels={"outcome": "folded"}).observe(
                                  float(dtau))
            partials_folded[a] += 1
            reg.counter("fleetsim.async_partials_folded_total").inc()
            version += 1
            ring[version] = self.server_state.params
            for v in [v for v in ring if v < version - max_staleness]:
                del ring[v]
            for dd, _ in batch:
                redispatch(dd, now)

            rec = {
                "aggregation": base_len + agg,
                "model_version": version,
                "buffer_size": k_ship,
                "staleness_mean": float(np.mean(stalenesses)),
                "staleness_max": int(np.max(stalenesses)),
                "discarded": discarded_partials,
                "contributors": len(batch),
                "train_loss": out["train_loss"],
                "total_weight": out["total_weight"],
                "sim_time_min": now,
                "arrival_rate_per_min": arrivals / max(now, 1e-9),
                "agg_rate_per_min": (agg + 1) / max(now, 1e-9),
                "wasted_updates_total": wasted,
                "agg_time_s": time.perf_counter() - t0,
                # Tree keys (absent from flat async records).
                "aggregators": aggregators,
                "agg_id": int(a),
                "agg_buffer_k": int(ks[a]),
                "agg_fold_tracking_min": tracking_min(),
            }
            reg.gauge("fleetsim.async_buffer_size").set(ks[a])
            reg.gauge("fleetsim.async_arrival_rate_per_min").set(
                sum(e.rate() for e in ests))
            if observe:
                rec["arrival_rate_ewma_per_min"] = round(
                    sum(e.rate() for e in ests), 6)
                rec["mass_folded"] = round(mass_folded, 6)
                rec["mass_discarded"] = round(mass_discarded, 6)
                hs = reg.histogram(
                    "fleetsim.async_staleness",
                    labels={"outcome": "folded"}).summary()
                if hs.get("count"):
                    rec["staleness_p50"] = hs["p50"]
                    rec["staleness_p90"] = hs["p90"]
                    rec["staleness_p99"] = hs["p99"]
            if prune_after > 0:
                rec["pruned"] = len(pruned)
                rec["pruned_total"] = pruned_total
            if conv_sig:
                rec.update(conv_sig)
            reg.counter("fleetsim.async_aggregations_total").inc()
            self.history.append(rec)
            if log_fn is not None:
                log_fn(rec)
        reg.gauge("fleetsim.async_sim_minutes").set(now)
        reg.histogram("fleetsim.round_time_s").observe(
            time.perf_counter() - start)
        return self.history
