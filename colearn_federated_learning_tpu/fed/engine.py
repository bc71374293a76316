"""Federated round orchestration, fully on-device.

This replaces the reference's coordinator process (SURVEY.md §3a: MQTT
enrollment → websocket broadcast → per-worker PyTorch epochs → host-side
``fed_avg``) with a single jit-compiled round function:

- single chip: clients are a ``vmap`` axis,
- multi chip:  clients are a ``shard_map`` axis over a ``jax.sharding.Mesh``
  and the weighted average lowers to ``jax.lax.psum`` over ICI
  (BASELINE.json ``north_star``).

One call = one federated round: cohort sampling → broadcast (implicit: the
global params are an operand) → local SGD per client → privacy hooks →
weighted aggregation → server update.  Shapes are static across rounds, so
the program compiles once.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from colearn_federated_learning_tpu.data import registry as data_registry
from colearn_federated_learning_tpu.data.sharding import (
    ClientShards,
    pack_client_shards,
    pad_clients_to_multiple,
)
from colearn_federated_learning_tpu.fed import programs
from colearn_federated_learning_tpu.fed import setup as setup_lib
from colearn_federated_learning_tpu.fed import strategies
from colearn_federated_learning_tpu.fed.evaluation import (
    detection_report,
    eval_rows,
    make_confusion_eval_fn,
    make_eval_fn,
)
from colearn_federated_learning_tpu.models import registry as model_registry
from colearn_federated_learning_tpu.privacy.accountant import RdpAccountant
from colearn_federated_learning_tpu import telemetry
from colearn_federated_learning_tpu.utils import prng
from colearn_federated_learning_tpu.utils import config as config_lib
from colearn_federated_learning_tpu.utils.config import ExperimentConfig


def _resolve_devices(backend: str) -> list:
    """Device list for --backend=auto|cpu|tpu.  ``auto`` is whatever
    ``jax.devices()`` returns, and a backend that fails to initialize
    raises: a run never lands on another device than the one it names."""
    devices = jax.devices()
    if backend == "auto":
        return devices
    if backend == "cpu":
        return [d for d in devices if d.platform == "cpu"] or jax.devices("cpu")
    if backend == "tpu":
        tpu = [d for d in devices if d.platform != "cpu"]
        if not tpu:
            raise RuntimeError("--backend=tpu requested but no accelerator present")
        return tpu
    raise ValueError(f"unknown backend {backend!r} (use auto|cpu|tpu)")


class FederatedLearner:
    """End-to-end federated experiment: data, model, round loop, eval.

    ``mesh``: optional ``jax.sharding.Mesh``.  The ``config.run.mesh_axis``
    (clients) axis is required; a ``seq`` axis adds ring-attention sequence
    parallelism, and a ``model`` axis adds GSPMD tensor/expert parallelism
    (parallel/tp.py) — any combination up to the 3-D
    (clients, seq, model) mesh.  Client state shards over the client axis
    and aggregation runs as psum over it.  When None, everything runs on
    one device via vmap.
    """

    @classmethod
    def from_config(
        cls,
        config: ExperimentConfig,
        dataset: Optional[data_registry.Dataset] = None,
    ) -> "FederatedLearner":
        """Build a learner honoring ``config.run.backend`` (the CLI's
        ``--backend=tpu|cpu|auto``, BASELINE.json ``north_star``): resolve
        devices and lay clients over a 1-D mesh — or, with
        ``attn_impl="ring"``, a 2-D (clients, seq) mesh, or, with
        ``run.tp_size > 1``, a 2-D (clients, model) tensor-parallel
        mesh."""
        from colearn_federated_learning_tpu.parallel.mesh import make_mesh

        devices = _resolve_devices(config.run.backend)
        r = config.run
        if config.model.attn_impl in ("ring", "ulysses") and r.tp_size > 1:
            raise ValueError(
                "from_config cannot auto-lay a 3-D (clients, seq, model) "
                "mesh; build it with parallel.mesh.make_mesh and pass "
                "mesh= explicitly"
            )
        mesh = None
        if r.tp_size > 1 and len(devices) % r.tp_size != 0:
            # Non-divisible device counts would otherwise surface as an
            # opaque reshape error inside make_mesh((-1, tp_size)).  The
            # degradation is observable: a warning for interactive runs
            # AND a labeled counter for dashboards/soaks — a fleet that
            # silently runs replicated at tp_size=1 is a perf SLO bug.
            import warnings

            telemetry.get_registry().counter(
                "fed.mesh_fallback_total",
                labels={"reason": "indivisible_devices"}).inc()
            warnings.warn(
                f"tp_size={r.tp_size} needs a device count that is a "
                f"multiple of it, have {len(devices)}; running without "
                f"tensor parallelism",
                stacklevel=2,
            )
        if len(devices) > 1:
            if config.model.attn_impl in ("ring", "ulysses"):
                mesh = make_mesh((r.mesh_axis, r.seq_axis), devices=devices)
            elif r.tp_size > 1 and len(devices) % r.tp_size == 0:
                mesh = make_mesh((r.mesh_axis, r.tp_axis), (-1, r.tp_size),
                                 devices=devices)
            else:
                mesh = Mesh(np.array(devices), (r.mesh_axis,))
        return cls(config, dataset=dataset, mesh=mesh)

    def __init__(
        self,
        config: ExperimentConfig,
        dataset: Optional[data_registry.Dataset] = None,
        mesh: Optional[Mesh] = None,
        partitions: Optional[list] = None,
    ):
        """``partitions``: optional explicit per-client index lists into the
        dataset's train split, overriding ``config.data.partition`` —
        callers that already know exactly who owns which rows (clustered
        FL preserving member shards) inject them here."""
        # The process-wide tracer: span() always times, so the phase
        # durations reach the metrics JSONL whatever happens, and always
        # annotates an open jax profile; spans are kept only inside a
        # recording window, which fit() opens (telemetry/lifecycle.py).
        self.tracer = telemetry.get_tracer()
        self._span_owner = object()      # whose spans the tracer's buffer holds
        self.last_trace_path: Optional[str] = None
        # What building a learner compiles, it compiles eagerly (model
        # init, placement): cache hits and misses count under this name.
        with self.tracer.span("from_config") as sp, telemetry.tracked_call(
                "engine.from_config"):
            self._build(config, dataset, mesh, partitions)
        telemetry.get_registry().gauge("engine.from_config_s").set(
            sp.duration_s)

    def _build(self, config, dataset, mesh, partitions):
        """One direction: mesh axes → data → model and server state → the
        round's plan → local trainer → programs → placement."""
        self.config = config
        self.mesh = mesh
        c = config
        config_lib.validate_experiment(c)
        self._read_mesh_axes()
        self.dataset = dataset or data_registry.get_dataset(
            c.data.dataset, seed=c.run.seed
        )
        self._pack_shards(partitions)
        self._init_model()
        # Every check on the federation's options is plan_round's; its
        # cohort warning is attributed to the caller of FederatedLearner().
        self.plan = programs.plan_round(
            c, num_clients=self.num_clients,
            real_num_clients=self.real_num_clients,
            num_steps=setup_lib.num_steps_for_config(c, self.shards.capacity),
            mesh=mesh, stacklevel=4)
        self.cohort_size = self.plan.cohort_size
        self.cohort_per_device = self.plan.cohort_per_device
        self.num_steps = self.plan.num_steps
        self.local_update = self._local_trainer(c)
        # SCAFFOLD per-client control variates: one params-shaped pytree per
        # client, stacked on the client axis — resident on HOST (numpy).
        # Each round gathers only the COHORT's variates into the jit round
        # program and scatters the updated block back, so device memory is
        # O(cohort × model), not O(num_clients × model) — the flagship
        # configs (thousands of clients × ViT) never fit the full stack.
        self.client_c = jax.tree.map(
            lambda w: np.zeros((self.num_clients,) + w.shape, w.dtype),
            self.params,
        ) if self.plan.scaffold else None
        # The clip norm is a device scalar: an operand of every round and,
        # under adaptive clipping, a metric fed into the next.
        self._dp_clip = self._on_mesh(jnp.float32(c.fed.dp_clip))
        # RDP accountant: cumulative (ε, δ) per round when DP is on
        # (privacy/accountant.py; each round is one subsampled Gaussian
        # mechanism with q = cohort / N at central noise σ).
        self.accountant = RdpAccountant.from_config(
            c.fed, sampling_rate=self.plan.dp_cohort / self.real_num_clients
        )
        self.base_key = prng.experiment_key(c.run.seed)
        # CompileTracker fingerprints every call's abstract signature: the
        # expected first compile lands in telemetry.compile_total with the
        # seconds it blocked in telemetry.compile_seconds, any LATER new
        # signature is a recompile with an attributed reason
        # (telemetry.recompile_total{fn,reason}) — a coordinator silently
        # recompiling every round becomes a visible counter + round-record
        # field.  Attribute access (.lower) passes through to the jitted fn.
        self._round_fn = telemetry.CompileTracker(
            programs.build_round_fn(
                self.plan, self.local_update,
                jax.tree.map(lambda l: l.sharding, self.server_state)),
            name="engine.round")
        self._eval_fn = telemetry.CompileTracker(
            self._build_eval_fn(), name="engine.eval")
        self._device_data = self._place_data()
        self.history: list[dict] = []
        self._ckpt = None

    def _read_mesh_axes(self):
        """1-D mesh: clients only.  2-D (attn_impl="ring"): + an inner
        ``seq`` axis (sequence parallelism; parallel/ring.py).  A ``model``
        axis (parallel/tp.py) adds tensor/expert parallelism: it is left to
        the AUTOMATIC partitioner (shard_map axis_names excludes it), params
        are sharded over it by the TP rules, and XLA inserts the TP
        collectives inside each client's local step."""
        c, mesh = self.config, self.mesh
        axes = (c.run.mesh_axis, c.run.seq_axis, c.run.tp_axis)
        shape = mesh.shape if mesh is not None else {}
        if mesh is not None and axes[0] not in shape:
            raise ValueError(
                f"mesh axes {tuple(shape)} lack the client axis "
                f"{axes[0]!r}"
            )
        extra = set(shape) - set(axes)
        if extra:
            raise ValueError(f"unsupported mesh axes {sorted(extra)}")
        self.seq_size = shape.get(c.run.seq_axis, 1)
        self.tp_size = shape.get(c.run.tp_axis, 1)
        self.sp = self.seq_size > 1
        if self.sp and c.model.attn_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"a {self.seq_size}-way {c.run.seq_axis!r} mesh axis requires "
                "model.attn_impl='ring' or 'ulysses'"
            )
        if (c.model.attn_impl in ("ring", "ulysses") and mesh is not None
                and not self.sp):
            raise ValueError(
                f"attn_impl={c.model.attn_impl!r} on a mesh requires a "
                f"{c.run.seq_axis!r} axis of size > 1"
            )

    def _pack_shards(self, partitions):
        """The train split packed into per-client shards: ``shards``,
        ``client_ids``, ``num_clients`` (slots) and ``real_num_clients``."""
        c = self.config
        labels = np.asarray(self.dataset.y_train)
        parts = (partitions if partitions is not None
                 else setup_lib.partition_for_config(c, labels))
        shards = pack_client_shards(
            np.asarray(self.dataset.x_train), labels, parts,
            capacity=c.data.max_examples_per_client,
        )
        self.real_num_clients = shards.num_clients   # pre-ghost-padding
        if self.sp:
            seq_len = shards.x.shape[-1]
            if shards.x.ndim != 3:
                raise ValueError(
                    "sequence parallelism needs (tokens,)-shaped examples, "
                    f"got example shape {shards.x.shape[2:]}"
                )
            if seq_len % self.seq_size:
                raise ValueError(
                    f"seq_len {seq_len} is not divisible by the "
                    f"{self.seq_size}-way {c.run.seq_axis!r} axis"
                )
            if (c.model.attn_impl == "ulysses"
                    and c.model.num_heads % self.seq_size):
                # Fail eagerly like the seq_len check above — the kernel's
                # own guard would only fire deep inside the first trace.
                raise ValueError(
                    f"attn_impl='ulysses' needs num_heads "
                    f"({c.model.num_heads}) divisible by the "
                    f"{self.seq_size}-way {c.run.seq_axis!r} axis; use "
                    "attn_impl='ring'"
                )
        if self.mesh is not None:
            D = self.mesh.shape[c.run.mesh_axis]
            shards = pad_clients_to_multiple(shards, D)
            # Interleave so real clients spread evenly across devices (ghost
            # padding would otherwise pile onto the last devices and starve
            # their per-device cohorts).  ``client_ids[slot]`` is the
            # ORIGINAL client identity of each array slot; all PRNG is keyed
            # on it, keeping results placement-independent.
            L = shards.num_clients // D
            order = np.array(
                [j * D + d for d in range(D) for j in range(L)], dtype=np.int32
            )
            shards = ClientShards(
                x=shards.x[order], y=shards.y[order], counts=shards.counts[order]
            )
            self.client_ids = order
        else:
            self.client_ids = np.arange(shards.num_clients, dtype=np.int32)
        self.shards = shards
        self.num_clients = shards.num_clients

    def _init_model(self):
        """Under SP the trained module runs on sequence SHARDS inside
        shard_map; its dense-attention twin (identical param pytree) is
        used for init and full-sequence evaluation outside the mesh."""
        c = self.config
        train_model_cfg = (
            c.model if self.sp else setup_lib.local_model_config(c.model)
        )
        self.model = model_registry.build_model(
            train_model_cfg, seq_axis_name=c.run.seq_axis if self.sp else None
        )
        if self.sp:
            self.eval_model = model_registry.build_model(
                setup_lib.local_model_config(c.model)
            )
        else:
            self.eval_model = self.model
        example_x = jnp.asarray(self.shards.x[0, : c.fed.batch_size])
        ikey = prng.init_key(prng.experiment_key(c.run.seed))
        self.params = model_registry.init_params(self.eval_model, example_x, ikey)
        if self.tp_size > 1:
            # Tensor parallelism: shard the wide param dims over the model
            # axis (parallel/tp.py rules); ``init_server_state``'s
            # zeros_like leaves inherit the shardings, so the whole server
            # state lives TP-sharded from the start.
            from colearn_federated_learning_tpu.parallel import tp as tp_lib

            self.params = tp_lib.shard_params(self.params, self.mesh,
                                              c.run.tp_axis)
        self.server_state = self._on_mesh(
            strategies.init_server_state(self.params, c.fed))

    def _local_trainer(self, config):
        """``config``'s ``local_update`` around the trained module, with
        this learner's mesh wiring: gradients synchronised over ``seq``
        under SP, parameters laid over ``model`` under TP."""
        update, _ = setup_lib.local_trainer_for_config(
            config, self.model.apply, self.shards.capacity,
            grad_sync_axes=(config.run.seq_axis,) if self.sp else (),
            param_axes=(config.run.tp_axis,) if self.tp_size > 1 else (),
        )
        return update

    # ------------------------------------------------------------------
    # data placement
    # ------------------------------------------------------------------
    @property
    def devices(self) -> list:
        """The devices the round program runs on, read off the server
        parameters themselves (every device of the mesh, or the one)."""
        leaf = jax.tree.leaves(self.server_state.params)[0]
        return sorted(leaf.devices(), key=lambda d: d.id)

    def _on_mesh(self, tree):
        """Round-program operands that come back out of the program
        (server state, adaptive clip) start where the program leaves
        them: replicated over the mesh, except leaves already laid over
        it (TP-sharded params).  jit keys its executables on argument
        placement, so state left on one device costs a second full
        compile of the round program in round 1.  Identity off-mesh."""
        if self.mesh is None:
            return tree
        replicated = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda leaf: leaf if isinstance(leaf.sharding, NamedSharding)
            else jax.device_put(leaf, replicated), tree)

    def _place_data(self):
        with self.tracer.span("h2d_transfer") as sp:
            x, y = self.shards.x, self.shards.y
            counts, ids = self.shards.counts, self.client_ids
            if self.mesh is not None:
                # Straight from host memory to each device's own block:
                # staging the whole array on one device first would make
                # that device hold every client's data.
                x = jax.device_put(
                    x, NamedSharding(self.mesh, self.plan.x_spec))
                sh = NamedSharding(self.mesh, P(self.plan.client_axis))
                y, counts, ids = (
                    jax.device_put(a, sh) for a in (y, counts, ids)
                )
            else:
                x, y, counts, ids = (
                    jnp.asarray(a) for a in (x, y, counts, ids))
            y, counts, ids = jax.block_until_ready((y, counts, ids))
            x = jax.block_until_ready(x)
        telemetry.get_registry().gauge("engine.h2d_transfer_s").set(
            sp.duration_s
        )
        return (x, y, counts, ids)

    # ------------------------------------------------------------------
    # compiled programs: construction lives in fed/programs.py (round
    # program vmap/mesh builders, per-client eval, personalization,
    # similarity) -- the engine only orchestrates.
    # ------------------------------------------------------------------
    def _build_client_eval_fn(self):
        # Kept as a method: clustered FL scores every cluster's model with
        # the base learner's program through it (fed/clustered.py).  The
        # shard data arrives as placed for training, sequence-sharded under
        # SP, so the trained module scores it.
        return programs.build_client_eval_fn(
            self.plan, self.model.apply, self.shards.capacity,
            eval_rows(self.config.fed.batch_size, self.shards.x[0]))

    # ------------------------------------------------------------------
    # evaluation (held-out global test set, SURVEY.md §3d)
    # ------------------------------------------------------------------
    def _build_eval_fn(self):
        return self._holdout_program(make_eval_fn(
            self.eval_model.apply,
            self.dataset.x_test,
            self.dataset.y_test,
            batch=eval_rows(self.config.fed.batch_size,
                            self.dataset.x_test),
        ))

    def _holdout_program(self, fn):
        """``fn(params)`` over the global holdout, as run on this learner's
        devices.  On a mesh the params arrive replicated over it, which
        turns a plain jit into an automatically partitioned program — and
        a Mosaic kernel (``attn_impl="flash"``) cannot be partitioned
        automatically.  So the mesh runs it manually over every axis with
        everything replicated: each device scores the full holdout, which
        is what the partitioner did anyway.  TP-sharded params stay with
        the partitioner, whose collectives they need."""
        if self.mesh is None or self.tp_size > 1:
            return fn
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=P(),
                                     out_specs=P(), check_vma=False))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def _host_sample_cohort(self, round_idx: int):
        """Cohort selection on HOST — the program's own draw
        (``programs.draw_cohort``) run eagerly, so the scaffold path can
        gather the cohort's variate rows before dispatching the round.

        Returns ``(sel, rows)``: ``sel`` are the per-device-local slot
        indices the round program consumes; ``rows`` the absolute rows of
        the (interleaved) client-stacked arrays, for host gather/scatter.
        """
        r = jnp.asarray(round_idx, jnp.int32)
        counts = jnp.asarray(self.shards.counts)

        def draw(block, device=None):
            return np.asarray(programs.draw_cohort(
                self.plan, self.base_key, r, block, device)).astype(np.int32)

        if self.mesh is None:
            sel = draw(counts)
            return sel, sel
        L = self.plan.local_clients
        sels = [draw(counts[d * L:(d + 1) * L], d)
                for d in range(self.plan.clients_size)]
        rows = [d * L + s for d, s in enumerate(sels)]
        return np.concatenate(sels), np.concatenate(rows)

    def run_round(self) -> dict:
        """One federated round, its metrics read back to the host."""
        out, phases = self._dispatch_round()
        with self.tracer.span("bookkeeping", round=len(self.history)):
            return self._record_round(out, phases)

    def _dispatch_round(self) -> tuple[dict, tuple]:
        """Enqueue the round program and read its metrics: the round's
        metrics and the spans its record takes its phase fields from."""
        r = len(self.history)
        if self.plan.scaffold:
            # Gather the cohort's variates from the host store; scatter the
            # refreshed block back afterwards (device memory stays
            # O(cohort × model)).
            with self.tracer.span("cohort_sample", round=r) as sample_sp:
                sel, rows = self._host_sample_cohort(r)
                c_cohort = jax.tree.map(lambda l: l[rows], self.client_c)
                sel_dev = jnp.asarray(sel)
                if self.mesh is not None:
                    sh = NamedSharding(self.mesh, P(self.plan.client_axis))
                    sel_dev = jax.device_put(sel_dev, sh)
                    c_cohort = jax.tree.map(
                        lambda l: jax.device_put(jnp.asarray(l), sh), c_cohort
                    )
        else:
            # The non-scaffold cohort is sampled INSIDE the jit program.
            sel, rows, sel_dev, c_cohort = None, None, None, None
            sample_sp = None
        # The round program is ONE fused jit call (sample → local SGD →
        # aggregate → server update), and the call returns when it is
        # enqueued: the span is what the host spends getting a round onto
        # the device, recording or not.  When the device ran it is in the
        # jax profile, where this span is an annotation on the same clock.
        with self.tracer.span("enqueue", round=r,
                              cohort=self.cohort_size) as update_sp:
            self.server_state, metrics, new_c = self._round_fn(
                self.server_state,
                self.base_key,
                jnp.asarray(r, jnp.int32),
                *self._device_data,
                sel_dev,
                c_cohort,
                self._dp_clip,
            )
        if self.plan.adaptive_clip:
            # Feed the adapted clip into the next round as a device scalar
            # (no host round-trip).
            self._dp_clip = metrics["dp_clip"]
        if self.plan.scaffold:
            with self.tracer.span("scatter_variates", round=r):
                updated = jax.tree.map(np.asarray, new_c)

                def scatter(full, upd):
                    full[rows] = upd
                    return full

                self.client_c = jax.tree.map(scatter, self.client_c, updated)
        with self.tracer.span("sync_metrics", round=r) as sync_sp:
            # ONE batched device→host transfer for the whole metrics
            # dict instead of a blocking read per scalar.
            out = {k: float(v)
                   for k, v in jax.device_get(metrics).items()}
        return out, (update_sp, sync_sp, sample_sp)

    def _record_round(self, out: dict, phases: tuple) -> dict:
        """Make the round's metrics its record and append it to the
        history; the caller holds the ``bookkeeping`` span open."""
        update_sp, sync_sp, sample_sp = phases
        out["round"] = len(self.history)
        out["phase_update_s"] = update_sp.duration_s
        out["phase_sync_s"] = sync_sp.duration_s
        if sample_sp is not None:
            out["phase_cohort_sample_s"] = sample_sp.duration_s
        # Key present only when something went wrong — a healthy run's
        # records stay byte-identical (tested layout contract).
        if self._round_fn.recompiles:
            out["recompiles"] = self._round_fn.recompiles
        telemetry.get_registry().counter("engine.rounds_total").inc()
        if self.accountant is not None:
            self.accountant.step()
            out["dp_epsilon"] = self.accountant.epsilon()
            out["dp_delta"] = self.accountant.delta
        self.history.append(out)
        return out

    def evaluate(self) -> tuple[float, float]:
        loss, acc = self._eval_fn(self.server_state.params)
        return float(loss), float(acc)

    def evaluate_detection(self, benign_class: int = 0) -> dict:
        """Detection-oriented held-out report (per-class P/R/F1, macro-F1,
        alarm detection/false-alarm rates) — the metrics the reference's
        IoT anomaly deployment cares about, where accuracy alone hides an
        always-benign classifier.  One jit scan accumulating the global
        confusion matrix; host-side summarization
        (fed/evaluation.detection_report)."""
        if not hasattr(self, "_conf_eval_fn"):
            self._conf_eval_fn = self._holdout_program(
                make_confusion_eval_fn(
                    self.eval_model.apply,
                    self.dataset.x_test,
                    self.dataset.y_test,
                    batch=eval_rows(self.config.fed.batch_size,
                                    self.dataset.x_test),
                    num_classes=self.config.model.num_classes,
                ))
        conf = np.asarray(self._conf_eval_fn(self.server_state.params))
        return detection_report(conf, benign_class=benign_class)

    # ---- federated (per-client) evaluation ---------------------------
    def evaluate_per_client(self) -> dict:
        """Score the CURRENT global model on every client's local shard.

        The reference's evaluator role scores one held-out set (SURVEY.md
        §3d); this is the federated-native complement — the model's fit to
        each client's own distribution, the quantity that matters under
        non-IID partitions.  One jit program, vmapped over clients (and
        sharded over the client axis on a mesh); returns per-client arrays
        in ORIGINAL client-id order plus weighted aggregates and the
        across-client accuracy spread.
        """
        if not hasattr(self, "_client_eval_fn"):
            self._client_eval_fn = self._build_client_eval_fn()
        loss, acc = self._client_eval_fn(
            self.server_state.params, *self._device_data[:3]
        )
        loss, acc = np.asarray(loss), np.asarray(acc)
        counts = np.asarray(self.shards.counts)
        # Undo the mesh interleaving, drop ghost clients.
        order = np.argsort(self.client_ids, kind="stable")
        loss, acc, counts = loss[order], acc[order], counts[order]
        real = counts > 0
        loss, acc, counts = loss[real], acc[real], counts[real]
        from colearn_federated_learning_tpu.fed.evaluation import (
            summarize_per_client,
        )

        out = summarize_per_client(loss, acc, counts)
        out.update(per_client_loss=loss, per_client_acc=acc,
                   num_examples=counts)
        return out

    # ---- client update similarity (clustered FL) ----------------------
    def client_update_similarity(self, steps: int = 1) -> np.ndarray:
        """(N, N) cosine similarity of every client's local update from
        the CURRENT global model — the clustering signal of clustered FL
        (fed/clustered.py): clients drawn from the same concept produce
        aligned updates, concept-shifted clients anti-align.

        One jit program: vmapped local steps over ALL clients, flatten,
        one gram matmul (MXU).  On the vmap path the (N, P) matrix never
        leaves the device.  On a mesh each device trains only ITS client
        block, L2-normalizes the (N/D, P) rows, all_gathers the
        normalized deltas over the client axis (robust aggregation pays
        the same O(N·P) price — order statistics and gram matrices are
        not psum-decomposable), computes its (N/D, N) strip of the gram
        on the MXU, and the strips reassemble to the sharded (N, N)
        output; rows/cols are then returned to ORIGINAL client-id order
        with ghost padding dropped.
        """
        if self.plan.scaffold:
            raise NotImplementedError(
                "clustering uses the plain local trainer; run it with a "
                "stateless strategy"
            )
        if getattr(self, "_sim_key", None) != steps:
            self._sim_key = steps
            self._sim_fn = programs.build_similarity_fn(
                self.plan, self.local_update, steps)
        sim = np.asarray(self._sim_fn(
            self.server_state.params, *self._device_data, self.base_key
        ))
        if self.mesh is not None:
            # Undo the mesh interleaving on BOTH axes; drop ghost padding.
            keep = self.id_order_slots()
            sim = sim[np.ix_(keep, keep)]
        return sim

    def id_order_slots(self) -> np.ndarray:
        """Array-slot index of every REAL client, in original client-id
        order — the inverse of the mesh interleaving with ghost padding
        dropped; the identity on the vmap path.

        Ghosts are identified by id (``id >= real_num_clients``: padding
        appends them after the real clients), NOT by ``counts == 0`` — a
        real client whose partition happens to be empty must keep its
        slot so per-id indexing (clustered FL labels) stays aligned
        across engine paths."""
        if self.mesh is None:
            return np.arange(self.num_clients)
        ids = np.asarray(self.client_ids)
        order = np.argsort(ids, kind="stable")
        return order[:self.real_num_clients]

    # ---- personalized evaluation (fine-tune-then-eval) ----------------
    def evaluate_personalized(self, steps: int = 5,
                              lr: Optional[float] = None) -> dict:
        """Per-client personalization probe: fine-tune the CURRENT global
        model on the first half of each client's shard for ``steps`` local
        SGD steps, then score BOTH the global and the personalized model on
        the held-out second half.  The spread between the two is the value
        personalization adds under this partition — the FedPer-style
        question the reference cannot ask (its evaluator scores one global
        holdout).  One jit program, vmapped over clients (sharded over the
        client axis on a mesh).

        Clients with fewer than 2 examples have no holdout half and are
        dropped from the aggregates.
        """
        key = (steps, lr)
        if getattr(self, "_pers_eval_key", None) != key:
            # The fine-tune is the CONFIG's local trainer (same optimizer,
            # momentum, MoE aux loss, prox term) with the step budget and
            # lr overridden.
            fed = self.config.fed
            fine_tune = self._local_trainer(self.config.replace(
                fed=dataclasses.replace(
                    fed,
                    strategy="fedprox" if fed.strategy == "fedprox"
                    else "fedavg",
                    local_steps=steps, lr=lr if lr is not None else fed.lr,
                    straggler_prob=0.0)))
            self._pers_eval_fn = programs.build_personalized_eval_fn(
                self.plan, self.model.apply, fine_tune, self.shards.capacity,
                eval_rows(fed.batch_size, self.shards.x[0]), self.base_key,
                steps)
            self._pers_eval_key = key
        g_acc, p_acc, n_eval = self._pers_eval_fn(
            self.server_state.params, *self._device_data
        )
        g_acc, p_acc = np.asarray(g_acc), np.asarray(p_acc)
        n_eval = np.asarray(n_eval)
        order = np.argsort(self.client_ids, kind="stable")
        g_acc, p_acc, n_eval = g_acc[order], p_acc[order], n_eval[order]
        real = n_eval > 0
        g_acc, p_acc, n_eval = g_acc[real], p_acc[real], n_eval[real]
        if n_eval.sum() == 0:
            # No client holds the >= 2 examples a holdout half needs.
            return {
                "global_acc": 0.0, "personalized_acc": 0.0,
                "personalization_gain": 0.0,
                "per_client_global_acc": g_acc,
                "per_client_personalized_acc": p_acc,
                "num_eval_examples": n_eval,
                "num_clients_evaluated": 0,
            }
        w = n_eval / n_eval.sum()
        return {
            "global_acc": float((g_acc * w).sum()),
            "personalized_acc": float((p_acc * w).sum()),
            "personalization_gain": float(((p_acc - g_acc) * w).sum()),
            "per_client_global_acc": g_acc,
            "per_client_personalized_acc": p_acc,
            "num_eval_examples": n_eval,
            "num_clients_evaluated": int(real.sum()),
        }

    # ---- checkpoint/resume (SURVEY.md §5; ckpt/manager.py) -----------
    def _checkpointer(self):
        if self._ckpt is None:
            from colearn_federated_learning_tpu.ckpt import RoundCheckpointer

            self._ckpt = RoundCheckpointer.for_run(self.config.run)
        return self._ckpt

    def save_checkpoint(self) -> None:
        # Scaffold's per-client variates are part of the training state and
        # checkpoint alongside the server state (None otherwise).
        self._checkpointer().save(
            len(self.history), (self.server_state, self.client_c), self.history
        )

    def restore_checkpoint(self) -> int:
        """Restore the latest checkpoint; returns the resumed round index."""
        state, history, step = self._checkpointer().restore(
            (self.server_state, self.client_c)
        )
        self.server_state, self.client_c = state
        self.history = history
        if self.accountant is not None:
            # ε must account for every round already spent before the kill.
            self.accountant.steps = step
        if self.plan.adaptive_clip and history:
            # The clip state rides the per-round metrics (one scalar per
            # record), so resume continues from the adapted norm.
            self._dp_clip = jnp.float32(history[-1]["dp_clip"])
        return step

    def fit(self, rounds: Optional[int] = None, log_fn=None) -> list[dict]:
        """Run ``rounds`` more federated rounds.  ``rounds=None`` means "up
        to the configured total": after a restore at round k, the default
        runs the REMAINING config.fed.rounds - k rounds, not a fresh full
        run."""
        if rounds is None:
            rounds = max(0, self.config.fed.rounds - len(self.history))
        last_round = len(self.history) + rounds - 1  # fit() may be called again
        telem = telemetry.RoundTelemetry(self.config.run, self.tracer,
                                         owner=self._span_owner)
        # The spans tile the call: what the host does between two
        # operations of the device has a name (PERF.md section 3).
        try:
            with self.tracer.span("fit", rounds=rounds):
                for _ in range(rounds):
                    t0 = time.perf_counter()
                    telem.before_round(len(self.history))
                    with self.tracer.span("round", round=len(self.history)):
                        rec = self._fit_round(t0, telem, last_round, log_fn)
                    # end_round AFTER the round span closed — an early
                    # window flush must include the final traced round.
                    telem.end_round(rec["round"])
        finally:
            # An exception mid-window (eval/log/ckpt) must not leave the
            # process-global jax profiler trace running, and whatever spans
            # were recorded still reach disk.
            self.last_trace_path = telem.close()
        return self.history

    def _fit_round(self, t0: float, telem, last_round: int, log_fn) -> dict:
        """One round of ``fit()``: ``run_round`` with the loop's own
        bookkeeping under the same span, then evaluation, logging and
        checkpoint where their cadence says so."""
        run = self.config.run
        eval_every = max(1, run.eval_every)
        log_every = max(1, run.log_every)
        ckpt_every = max(0, run.checkpoint_every)
        out, phases = self._dispatch_round()
        r = len(self.history)
        with self.tracer.span("bookkeeping", round=r):
            rec = self._record_round(out, phases)
            telem.after_round(r)
            rec["round_time_s"] = time.perf_counter() - t0
            # The key appears only when its source exists —
            # memory_stats() is empty on CPU — so default-run records
            # stay byte-identical (tested layout contract).
            stats = telemetry.sample_device_memory()
            if stats.get("bytes_in_use"):
                rec["hbm_used_gb"] = round(stats["bytes_in_use"] / 2**30, 3)
            telemetry.get_registry().histogram(
                "engine.round_time_s").observe(rec["round_time_s"])
        if r % eval_every == 0 or r == last_round:
            with self.tracer.span("evaluate", round=r) as ev_sp:
                loss, acc = self.evaluate()
            rec["eval_loss"], rec["eval_acc"] = loss, acc
            rec["phase_eval_s"] = ev_sp.duration_s
        if log_fn is not None and (r % log_every == 0 or r == last_round):
            with self.tracer.span("log", round=r):
                log_fn(rec)
        # With a checkpoint_dir, the final round ALWAYS checkpoints even
        # when no periodic cadence is configured, so --resume works.
        if run.checkpoint_dir and (
            (ckpt_every and (r + 1) % ckpt_every == 0) or r == last_round
        ):
            with self.tracer.span("checkpoint", round=r) as ck_sp:
                self.save_checkpoint()
            rec["phase_checkpoint_s"] = ck_sp.duration_s
        return rec
