"""Hierarchical (edge → cloud) federation — HierFAVG-style two-tier rounds.

CoLearn's deployment picture is IoT devices behind edge gateways; the
reference still aggregates FLAT (every device talks to the one
coordinator, SURVEY.md §3a).  This module adds the two-tier topology
(Liu et al. 1905.06641, client-edge-cloud pattern only): each EDGE GROUP
runs full federated rounds over its own client population — reusing the
jit round engine unchanged, one ``FederatedLearner`` per group — and every
``sync_period`` rounds the edge models average into the cloud model
(weighted by group example counts), which re-seeds every group.

Communication shape this buys at the edge: devices talk only to their
gateway every round; the WAN link carries one model per group every
``sync_period`` rounds — a 1/sync_period cut of the reference's
cloud-bound traffic.

Scope: cloud sync averages PARAMS, so the strategies whose server state is
exactly params (fedavg / fedprox) are supported; adaptive server
optimizers keep per-group moments that a param average would silently
desynchronise, and scaffold's variates live per-client — both are
rejected loudly.

Secure aggregation composes GROUP-LOCALLY here (DisAgg-style): each edge
group is its own ``FederatedLearner`` over ``clients_per_group`` clients,
so with ``fed.secure_agg`` on, pair masks (and the dropout-recovery share
fan-outs, privacy/dropout.py) span only the group — the per-device mask
cost is O(group + neighbors) instead of O(cohort), and the system-wide
pair count drops from O(cohort²) to O(cohort · group).  The cloud tier
averages already-unmasked group means, exactly like the plain path.
:meth:`HierarchicalLearner.mask_cost_summary` quantifies the cut.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from colearn_federated_learning_tpu.data import registry as data_registry
from colearn_federated_learning_tpu.faults import fileplane, inject
from colearn_federated_learning_tpu.fed.engine import FederatedLearner
from colearn_federated_learning_tpu.fed.evaluation import make_eval_fn
from colearn_federated_learning_tpu.telemetry import registry as _metrics
from colearn_federated_learning_tpu.utils import pytrees
from colearn_federated_learning_tpu.utils.config import ExperimentConfig


class HierarchicalLearner:
    """Two-tier federated simulation (see module docstring).

    ``num_groups`` edge groups each own a disjoint contiguous shard of the
    training corpus and ``num_clients // num_groups`` clients, partitioned
    within the group by the config's scheme (iid / dirichlet) — each edge
    domain is its own population, which is exactly the non-IID structure
    hierarchical FL exists for.
    """

    def __init__(self, config: ExperimentConfig, num_groups: int = 2,
                 sync_period: int = 2):
        if num_groups < 2:
            raise ValueError(f"num_groups must be >= 2, got {num_groups}")
        if sync_period < 1:
            raise ValueError(f"sync_period must be >= 1, got {sync_period}")
        if config.fed.strategy not in ("fedavg", "fedprox"):
            raise ValueError(
                "hierarchical sync averages params; strategy "
                f"{config.fed.strategy!r} carries extra server state "
                "(moments/variates) a param average would desynchronise"
            )
        self.config = config
        self.num_groups = num_groups
        self.sync_period = sync_period

        if config.data.num_clients % num_groups:
            raise ValueError(
                f"num_clients={config.data.num_clients} is not divisible "
                f"by num_groups={num_groups}; remainder clients would be "
                "silently dropped while their data still lands in a group"
            )
        base = data_registry.get_dataset(config.data.dataset,
                                         seed=config.run.seed)
        self.dataset = base        # registry branch visibility (disk/synth)
        n = len(base.y_train)
        clients_per_group = config.data.num_clients // num_groups
        self.groups: list[FederatedLearner] = []
        self.group_examples: list[int] = []
        for g in range(num_groups):
            lo = g * n // num_groups
            hi = (g + 1) * n // num_groups
            ds = dataclasses.replace(
                base,
                x_train=base.x_train[lo:hi], y_train=base.y_train[lo:hi],
            )
            gcfg = config.replace(
                data=dataclasses.replace(config.data,
                                         num_clients=clients_per_group),
                run=dataclasses.replace(
                    config.run, name=f"{config.run.name}_edge{g}",
                    # Distinct seeds de-correlate group cohort sampling /
                    # client PRNG streams (client ids restart at 0 in
                    # every group).
                    seed=config.run.seed * num_groups + g,
                ),
            )
            # from_config resolves --backend and lays any client mesh,
            # exactly like the flat path.
            self.groups.append(FederatedLearner.from_config(gcfg, dataset=ds))
            self.group_examples.append(int(np.asarray(ds.y_train).size))

        # Cloud model: start every group from the SAME init (group 0's).
        self.global_params = self.groups[0].params
        # Cloud aggregation as ONE jit program: eager per-leaf tree math
        # would dispatch one small device op per leaf per group.
        import jax

        w = np.asarray(self.group_examples, np.float64)
        ws = tuple(float(x) for x in (w / w.sum()))

        @jax.jit
        def _sync(group_params):
            acc = pytrees.tree_scale(group_params[0], ws[0])
            for wi, p in zip(ws[1:], group_params[1:]):
                acc = pytrees.tree_add(acc, pytrees.tree_scale(p, wi))
            return acc

        self._sync_fn = _sync
        self._seed_groups()
        self._eval_fn = make_eval_fn(
            self.groups[0].eval_model.apply, base.x_test, base.y_test,
            batch=max(config.fed.batch_size, 64),
        )
        self.history: list[dict] = []

    # ------------------------------------------------------------------
    def _seed_groups(self, round_idx: Optional[int] = None) -> None:
        faulted = inject.active_plan() is not None
        for i, g in enumerate(self.groups):
            if faulted and fileplane.should_drop(f"g{i}", round_idx,
                                                 fileplane.HOP_SEED):
                # Cloud→edge downlink lost: the group keeps training from
                # its own stale model until the next successful sync.
                continue
            g.server_state = g.server_state._replace(
                params=self.global_params
            )

    def _cloud_sync(self, round_idx: Optional[int] = None) -> list[str]:
        """Cloud aggregation: example-count-weighted mean of edge models.

        Under an installed FaultPlan, ``drop_silo`` specs keyed by group
        (``g0``, ``g1``, ...) on hop ``sync`` lose that group's uplink:
        the cloud mean renormalizes over the survivors (eager fallback —
        the jit path assumes the full fixed-weight cohort).  Returns the
        dropped group idents."""
        if inject.active_plan() is None:
            self.global_params = self._sync_fn(
                tuple(g.server_state.params for g in self.groups)
            )
            self._seed_groups()
            return []
        dropped: list[str] = []
        alive: list[tuple[float, object]] = []
        for i, g in enumerate(self.groups):
            ident = f"g{i}"
            if fileplane.should_drop(ident, round_idx, fileplane.HOP_SYNC):
                dropped.append(ident)
                _metrics.get_registry().counter(
                    "fed.hier_groups_dropped_total",
                    labels={"group": ident}).inc()
                continue
            alive.append((float(self.group_examples[i]), g.server_state.params))
        if alive:
            total = sum(w for w, _ in alive)
            acc = pytrees.tree_scale(alive[0][1], alive[0][0] / total)
            for w, p in alive[1:]:
                acc = pytrees.tree_add(acc, pytrees.tree_scale(p, w / total))
            self.global_params = acc
        # else: every uplink lost — the cloud model simply stays stale.
        self._seed_groups(round_idx)
        return dropped

    def run_round(self) -> dict:
        """One edge round in EVERY group; cloud sync on period boundaries."""
        r = len(self.history)
        recs = [g.run_round() for g in self.groups]
        synced = (r + 1) % self.sync_period == 0
        dropped: list[str] = []
        if synced:
            dropped = self._cloud_sync(r)
        out = {
            "round": r,
            "synced": synced,
            "train_loss": float(np.mean([x["train_loss"] for x in recs])),
            "completed": float(np.sum([x["completed"] for x in recs])),
            "group_losses": [float(x["train_loss"]) for x in recs],
        }
        if dropped:
            out["groups_dropped"] = dropped
        self.history.append(out)
        return out

    def mask_cost_summary(self) -> dict:
        """Per-device secure-agg cost of THIS topology vs the flat one.

        Pure arithmetic on :func:`privacy.dropout.mask_cost` — no masking
        has to be enabled to ask.  ``quadratic_ratio`` is the system-wide
        pair-count cut the two-tier topology buys (flat O(cohort²) pairs
        over grouped O(cohort · group)); bench_fleet's ``--mask-sweep``
        reports the same columns at the 1M-device point."""
        from colearn_federated_learning_tpu.privacy import dropout

        cohort = self.config.data.num_clients
        group = cohort // self.num_groups
        cost = dropout.mask_cost(
            cohort=cohort,
            param_count=pytrees.tree_size(self.global_params),
            neighbors=self.config.fed.secure_agg_neighbors,
            group_size=group,
        )
        cost["num_groups"] = self.num_groups
        cost["group_size"] = group
        cost["quadratic_ratio"] = (
            cost["flat_pairs_total"] / max(1, cost["grouped_pairs_total"])
        )
        return cost

    def evaluate(self) -> tuple[float, float]:
        """Cloud-model score on the global holdout.  Between syncs the
        cloud model is the LAST synced one; call after a sync boundary for
        the freshest aggregate."""
        loss, acc = self._eval_fn(self.global_params)
        return float(loss), float(acc)

    def fit(self, rounds: Optional[int] = None, log_fn=None) -> list[dict]:
        rounds = rounds if rounds is not None else self.config.fed.rounds
        run = self.config.run
        last_round = len(self.history) + rounds - 1
        for _ in range(rounds):
            rec = self.run_round()
            if rec["round"] == last_round and not rec["synced"]:
                # Terminal sync (standard HierFAVG): the reported final
                # model must fold the groups' last partial period, not a
                # stale cloud aggregate.
                dropped = self._cloud_sync(rec["round"])
                rec["synced"] = True
                if dropped:
                    rec["groups_dropped"] = dropped
            if rec["synced"]:
                loss, acc = self.evaluate()
                rec["eval_loss"], rec["eval_acc"] = loss, acc
            if log_fn is not None and (
                rec["round"] % max(1, run.log_every) == 0
                or rec["round"] == last_round
            ):
                log_fn(rec)
        return self.history


# ---- tree-async secure-agg groundwork (per-buffer mask cohorts) ----------
def buffer_mask_cohorts(assignment: dict, pruned=()) -> dict:
    """Per-buffer mask cohorts for the tree-async plane.

    ``assignment`` maps device id -> aggregator id (the async root's
    slice assignment).  Pairwise masks only cancel within a COMPLETE
    sum, and in tree-async mode each aggregator's buffer is folded (and
    staleness-discounted) as its own partial — so a mask pair must never
    span two buffers.  Each buffer therefore becomes its own pairing
    cohort, exactly the group-local math :meth:`HierarchicalLearner
    .mask_cost_summary` prices for the edge tier.

    ``pruned`` devices are excluded from the pair graph UP FRONT: a
    pruned client is a *predicted* dropout — the root pauses its pump
    before mask setup, it never commits a mask, and its absence costs
    zero share recoveries.  (A *reactive* dropout — a device that masks
    and then dies mid-buffer — costs its ``degree`` share recoveries,
    as on the sync plane.)

    Returns ``agg_id -> sorted device-id list`` (deterministic cohort
    order: the mask PRG seeds key off pair order).
    """
    cut = {str(d) for d in pruned}
    out: dict = {}
    for dev, aid in assignment.items():
        if str(dev) in cut:
            continue
        out.setdefault(aid, []).append(str(dev))
    return {aid: sorted(devs, key=str) for aid, devs in sorted(out.items())}


def async_mask_cost(assignment: dict, param_count: int,
                    neighbors: int = 0, pruned=()) -> dict:
    """Analytic secure-agg cost of the per-buffer cohort layout.

    Prices what :func:`buffer_mask_cohorts` buys: per-buffer pair
    degrees (each device's masks span only its buffer), the predicted-
    dropout accounting (pruned devices cost ZERO recoveries because
    they are excluded before mask commitment), and the per-buffer
    reactive-recovery bill a mid-buffer death would cost instead."""
    from colearn_federated_learning_tpu.privacy import dropout

    cohorts = buffer_mask_cohorts(assignment, pruned=pruned)
    active = sum(len(devs) for devs in cohorts.values())
    per_buffer: dict = {}
    pairs_total = 0
    for aid, devs in cohorts.items():
        if not devs:
            continue
        cost = dropout.mask_cost(
            cohort=max(1, active), param_count=param_count,
            neighbors=neighbors, group_size=len(devs))
        degree = cost["pairs_per_device"]
        per_buffer[aid] = {
            "devices": len(devs),
            "pairs_per_device": degree,
            "mask_flops_per_device": cost["mask_flops_per_device"],
            # What ONE reactive (mid-buffer) dropout in this buffer
            # would cost: its degree's worth of share recoveries.
            "reactive_recovery_shares": degree,
        }
        pairs_total += len(devs) * degree // 2
    predicted = sum(1 for d in assignment if str(d) in
                    {str(p) for p in pruned})
    return {
        "buffers": per_buffer,
        "active_devices": active,
        "pairs_total": pairs_total,
        "predicted_dropouts": predicted,
        # The headline: a predicted dropout never masked, so it costs
        # nothing to recover from — unlike a reactive one.
        "predicted_recovery_shares": 0,
    }
