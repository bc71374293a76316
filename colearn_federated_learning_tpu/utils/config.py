"""Experiment configuration.

The reference parameterizes its scripts with argparse flags (SURVEY.md §2
"Config/scripts": host/port, broker, rounds, epochs, lr, client count).  The
rebuild uses frozen dataclasses so a whole experiment is one hashable value
that can be threaded into jit as static configuration, and ships a registry
mirroring the five driver benchmark configs from BASELINE.json ``configs``.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Optional

# Dense/flash crossover (PERF.md §5): at seq 128 the Pallas flash kernel
# LOSES to dense on the config-#4 BERT (round 3, an earlier installation:
# 1.55 vs 2.12 rounds/sec; first contact on today's: 1.34 vs 1.73 at
# cohort 9) — tiling overhead only pays for itself once the O(L^2) score
# matrix stops fitting in VMEM, around L≈1-2k on v5-lite.  Below this
# length the guard warns; dense is both faster and numerically identical.
FLASH_SEQ_CROSSOVER = 1024


def validate_experiment(config: "ExperimentConfig") -> None:
    """Cross-field sanity checks for perf footguns.

    Warns rather than raises: every combination here EXECUTES correctly,
    it is just measured-slower than the obvious alternative, and a user
    sweeping configs must be able to override a heuristic.  Called by
    ``FederatedLearner.__init__`` so every entry path (CLI, from_config,
    direct construction) passes through it once."""
    m = config.model
    if m.attn_impl == "flash" and m.seq_len < FLASH_SEQ_CROSSOVER:
        warnings.warn(
            f"attn_impl='flash' at seq_len={m.seq_len}: dense attention is "
            f"measured FASTER below seq_len~{FLASH_SEQ_CROSSOVER} (PERF.md "
            "§5: at L=128 on the config-#4 BERT flash ran at about three "
            "quarters of dense's rounds/sec); use attn_impl='dense' unless "
            "you are measuring the kernel itself",
            # Attribute to validate_experiment's caller (engine __init__):
            # the call depth from user code varies (direct construction vs
            # from_config), so no fixed level reaches the user frame — the
            # message itself carries the identifying config values instead.
            stacklevel=2,
        )


def validate_robustness(config: "ExperimentConfig") -> None:
    """Hard checks on the comm-plane robustness knobs.  These RAISE
    (unlike :func:`validate_experiment`'s perf warnings): a quorum above
    1.0 or an eviction threshold of 0 is not a slow configuration, it is
    a meaningless one.  Called by both socket coordinators and the worker
    entrypoints."""
    run, fed = config.run, config.fed
    if run.evict_after < 1:
        raise ValueError(f"evict_after must be >= 1, got {run.evict_after}")
    if not 0.0 <= fed.min_cohort_fraction <= 1.0:
        raise ValueError(
            "min_cohort_fraction must be in [0, 1], got "
            f"{fed.min_cohort_fraction}"
        )
    if run.comm_retries < 0:
        raise ValueError(
            f"comm_retries must be >= 0, got {run.comm_retries}")
    if run.comm_backoff_base < 0 or run.comm_backoff_max < 0:
        raise ValueError("comm backoff values must be >= 0")
    if fed.lr_spike_round < -1:
        raise ValueError(
            f"lr_spike_round must be >= -1, got {fed.lr_spike_round}")
    if fed.lr_spike_multiplier <= 0:
        raise ValueError(
            "lr_spike_multiplier must be positive, got "
            f"{fed.lr_spike_multiplier}")
    if run.worker_enroll_timeout <= 0:
        raise ValueError(
            "worker_enroll_timeout must be positive, got "
            f"{run.worker_enroll_timeout}"
        )
    from colearn_federated_learning_tpu.fed.compression import SCHEMES

    if fed.compress not in SCHEMES:
        raise ValueError(
            f"unknown compress {fed.compress!r} (use {SCHEMES})"
        )
    if fed.compress_down not in SCHEMES:
        raise ValueError(
            f"unknown compress_down {fed.compress_down!r} (use {SCHEMES})"
        )
    if not 0.0 < fed.topk_fraction <= 1.0:
        raise ValueError(
            f"topk_fraction must be in (0, 1], got {fed.topk_fraction}"
        )
    if fed.secure_agg and fed.compress_feedback:
        raise ValueError(
            "secure_agg cannot carry uplink error feedback: masked updates "
            "are dense by construction (lossy compression would break the "
            "pairwise mask cancellation), so there is no compression "
            "residual to feed back"
        )
    if fed.topk_adaptive:
        if (fed.compress not in ("topk", "topk8")
                or not fed.compress_feedback):
            raise ValueError(
                "topk_adaptive steers density off the error-feedback "
                "residual norm, so it needs compress='topk'/'topk8' AND "
                "compress_feedback=True"
            )
        if not (0.0 < fed.topk_min_fraction
                <= fed.topk_max_fraction <= 1.0):
            raise ValueError(
                "topk_adaptive needs 0 < topk_min_fraction <= "
                "topk_max_fraction <= 1, got "
                f"[{fed.topk_min_fraction}, {fed.topk_max_fraction}]"
            )
    if fed.lora_rank < 0:
        raise ValueError(f"lora_rank must be >= 0, got {fed.lora_rank}")
    if fed.lora_rank > 0:
        if fed.lora_alpha <= 0:
            raise ValueError(
                f"lora_alpha must be positive, got {fed.lora_alpha}")
        if fed.lora_merge_every < 1:
            raise ValueError(
                "lora_merge_every must be >= 1, got "
                f"{fed.lora_merge_every}"
            )
        if fed.compress_down != "none":
            raise ValueError(
                "lora_rank > 0 replaces the broadcast with a base+factor "
                "frame; the downlink delta-cache protocol (compress_down) "
                "does not compose with it — factor uplink compression "
                "(fed.compress) is the supported knob"
            )
        if fed.strategy not in ("fedavg", "fedprox"):
            raise ValueError(
                "lora_rank > 0 folds FACTOR deltas, which the adaptive "
                "server optimizers' params-shaped moment state cannot "
                f"consume — use fedavg/fedprox, not {fed.strategy!r}"
            )
        # NOTE what is deliberately ALLOWED: compress="topk"/"topk8"
        # (+feedback / adaptive density) applies the sparse codec TO THE
        # FACTORS, and secure_agg masks the (dense) factor tree — the
        # secure_agg x compress conflict keeps its existing wire-plane
        # rejection (comm/worker.py __init__), identical under lora.
    if run.num_aggregators < 0:
        raise ValueError(
            f"num_aggregators must be >= 0, got {run.num_aggregators}")
    if run.num_aggregators and run.agg_heartbeat_timeout <= 0:
        raise ValueError(
            "agg_heartbeat_timeout must be positive, got "
            f"{run.agg_heartbeat_timeout}"
        )
    if run.agg_buffer_interval_s <= 0:
        raise ValueError(
            "agg_buffer_interval_s must be positive, got "
            f"{run.agg_buffer_interval_s}"
        )


@dataclasses.dataclass(frozen=True)
class DataConfig:
    dataset: str = "mnist"            # registry name (data/registry.py)
    num_clients: int = 10
    partition: str = "iid"            # "iid" | "dirichlet" | "pathological"
    dirichlet_alpha: float = 0.5      # non-IID skew (BASELINE config #2)
    max_examples_per_client: int = 0  # 0 = derive from dataset size


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "mlp"                 # models/registry.py name
    num_classes: int = 10
    # Family-specific knobs (ignored by families that don't use them):
    hidden_dim: int = 200             # MLP
    depth: int = 2                    # MLP layers / transformer blocks
    width: int = 64                   # CNN base channels / embed dim
    num_heads: int = 4                # transformers
    patch_size: int = 16              # ViT
    seq_len: int = 128                # text models
    vocab_size: int = 30522           # BERT wordpiece vocab size
    dtype: str = "float32"            # compute dtype ("bfloat16" on TPU)
    attn_impl: str = "dense"          # dense | flash (pallas) | ring/ulysses (SP)
    num_experts: int = 4              # MoE families (models/moe.py)
    moe_aux_weight: float = 0.01      # Switch load-balance loss weight
    # Rematerialize transformer blocks under autodiff (jax.checkpoint):
    # trades recompute FLOPs for activation HBM — how deep models fit
    # long local training on a chip.
    remat: bool = False
    # CNN MFU levers (PERF.md §5b: the north-star CNN sits near 25% MFU
    # with an op-mix explanation — the 3-channel stem conv wastes the
    # MXU's 128-lane contraction dim and GroupNorm is bandwidth-bound):
    # - stem="space_to_depth": fold 2x2 spatial patches into channels
    #   (32x32x3 -> 16x16x12) before the first conv — 4x fewer positions,
    #   4x more contraction channels, same receptive-field economics.
    # - norm="none": drop GroupNorm entirely (measure accuracy cost).
    # Defaults preserve the measured baseline model exactly.
    stem: str = "conv"                # conv | space_to_depth (CNN)
    norm: str = "group"               # group | none (CNN)
    # Causal decoder (models/evabyte.py): gated feed-forward width, EVA's
    # exact-attention window and summary chunk (ops/eva.py), next-byte
    # prediction heads (head j at position i predicts i + 1 + j), rotary
    # base.  ``vocab_size`` is its vocabulary, ``seq_len`` its context.
    ffn_dim: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    # Hybrid decoder (models/nemotron_h.py): one letter a layer (M Mamba-2,
    # E the chip's share of a LatentMoE layer, * grouped-query attention).
    # Mamba-2: heads of ``mamba_head_dim`` in ``mamba_groups`` groups that
    # share B and C, state and convolution widths (``chunk_size`` is the
    # scan's chunk, ops/ssd.py).  LatentMoE: ``num_experts`` is the whole
    # mixture the router scores, of which this chip holds ``experts_held``
    # from ``experts_first`` on; experts a token; the latent, expert and
    # shared-expert widths; the factor on the routed weights.  Attention:
    # ``num_heads`` query heads of ``head_dim`` on ``num_kv_heads``
    # key/value heads (0: as many as query heads; width / heads).
    layer_pattern: str = "MEMEMEM*EME"
    mamba_heads: int = 32
    mamba_head_dim: int = 64
    mamba_groups: int = 2
    ssm_state_size: int = 128
    conv_kernel: int = 4
    experts_first: int = 0
    experts_held: int = 8
    experts_per_token: int = 22
    latent_dim: int = 1024
    expert_dim: int = 2688
    shared_expert_dim: int = 5376
    routed_scale: float = 5.0
    num_kv_heads: int = 0
    head_dim: int = 0
    # Latent-attention decoder on a residual path of several streams
    # (models/xing4.py): ``depth`` layers, the first ``dense_layers`` with
    # a gated feed-forward of ``ffn_dim``, the others with this chip's
    # share of ``num_experts`` gated experts (``experts_first``,
    # ``experts_held``, ``experts_per_token``, ``expert_dim``,
    # ``shared_expert_dim``, ``routed_scale`` as above).  The path
    # (models/mhc.py): ``hc_streams`` rows a token, mixed by maps that
    # ``sinkhorn_iters`` iterations with ``sinkhorn_eps`` make doubly
    # stochastic from scores clipped to ``res_clamp_*``.  Attention
    # (models/mla.py): ``num_heads`` heads, the low ranks of the query and
    # key/value paths, a head's width without and with positions and its
    # values' width; ``rope_theta`` under yarn (``yarn_factor`` 1: plain
    # rotary).  ``mtp_modules`` sequential prediction modules (0 or 1):
    # the logits then have 1 + ``mtp_modules`` heads a position.
    dense_layers: int = 1
    hc_streams: int = 4
    sinkhorn_iters: int = 20
    sinkhorn_eps: float = 1e-6
    res_clamp_min: float = -30.0
    res_clamp_max: float = 30.0
    q_rank: int = 768
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    yarn_factor: float = 64.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    mtp_modules: int = 0
    norm_eps: float = 1e-6
    # Hybrid of Kimi delta attention and latent attention with gated
    # experts (models/ling3.py): ``depth`` layers held from the published
    # layer ``first_layer`` on; layer i of the published stack mixes by
    # latent attention where (i + 1) % ``layer_group_size`` is 0 (no query
    # rank; ``kv_rank``, ``nope_dim``, ``rope_dim``, ``v_dim``,
    # ``rope_theta`` as above) and by the delta rule elsewhere
    # (``num_heads`` heads of ``head_dim``, ``conv_kernel``, ``chunk_size``
    # positions a chunk, a log-decay a channel above ``kda_lower_bound``).
    # The experts are chosen within ``expert_groups_kept`` of
    # ``expert_groups`` groups; the share layer takes its tokens a
    # ``moe_token_block`` and its rows a ``moe_row_tile`` at a time;
    # ``expert_limits`` / ``shared_expert_limits``: a held layer's clamps
    # on its experts' and its shared expert's gate and up-projection
    # (empty: none).
    first_layer: int = 0
    layer_group_size: int = 6
    kda_lower_bound: float = -5.0
    expert_groups: int = 1
    expert_groups_kept: int = 1
    moe_token_block: int = 4096
    moe_row_tile: int = 4096
    expert_limits: tuple = ()
    shared_expert_limits: tuple = ()


@dataclasses.dataclass(frozen=True)
class FedConfig:
    strategy: str = "fedavg"          # fedavg | fedprox | fedadam | fedyogi | scaffold | fednova
    rounds: int = 20
    cohort_size: int = 0              # clients sampled per round; 0 = all
    local_epochs: int = 1
    local_steps: int = 0              # if >0 overrides epochs with a step budget
    batch_size: int = 32
    lr: float = 0.1
    # Client-lr schedule ACROSS ROUNDS (fed/strategies.lr_scale_for_round):
    # the per-step optimizer keeps ``lr`` but every update is scaled by an
    # in-graph factor computed from the round index — warmup ramps over
    # ``warmup_rounds``, cosine decays over the config's ``rounds`` horizon
    # to ``lr_min_fraction``·lr.  Constant lr was the round-3 text-config
    # bottleneck (curves cut off mid-climb).
    lr_schedule: str = "constant"     # constant | cosine | warmup_cosine
    warmup_rounds: int = 0
    lr_min_fraction: float = 0.0      # cosine floor as a fraction of lr
    momentum: float = 0.9
    local_optimizer: str = "sgd"      # sgd | adam | adamw (client-side)
    prox_mu: float = 0.0              # FedProx μ (BASELINE config #3: 0.01)
    server_lr: float = 1.0            # server-side step on the mean delta
    # Byzantine-robust aggregation (fed/robust.py): replaces the weighted
    # mean with an order statistic / distance-based selection over the
    # cohort (see robust.AGGREGATORS for the canonical list).
    aggregator: str = "mean"          # mean | median | trimmed_mean | krum
    # Per-side trim for trimmed_mean; the assumed Byzantine FRACTION f/n
    # for krum (both need floor(trim_fraction * cohort) >= 1).
    trim_fraction: float = 0.1
    # Hierarchical (edge -> cloud) federation (fed/hierarchical.py):
    # >= 2 edge groups run local rounds; cloud syncs every sync_period.
    edge_groups: int = 0              # 0/1 = flat federation
    edge_sync_period: int = 2
    server_beta1: float = 0.9         # FedAdam/FedYogi
    server_beta2: float = 0.99
    server_eps: float = 1e-3
    # Straggler handling (SURVEY.md §5 "failure detection"): each client gets
    # a per-round step budget; clients whose budget falls below
    # ``straggler_min_steps`` are dropped from the weighted average.
    straggler_prob: float = 0.0
    straggler_min_fraction: float = 0.25
    # Privacy hooks (BASELINE.json north_star: on-device DP + secure agg).
    dp_clip: float = 0.0              # 0 disables clipping
    dp_noise_multiplier: float = 0.0  # Gaussian sigma = mult * clip
    dp_delta: float = 1e-5            # δ at which the accountant reports ε
    # Adaptive clipping (quantile tracking; privacy/dp.py): dp_clip becomes
    # the INITIAL clip and follows the dp_target_quantile of update norms.
    dp_adaptive_clip: bool = False
    dp_target_quantile: float = 0.5
    dp_clip_lr: float = 0.2           # η_C of the geometric clip update
    dp_bit_noise: float = 0.0         # σ_b on the bit sum; 0 = cohort/20
    secure_agg: bool = False
    secure_agg_neighbors: int = 0     # 0 = all-pairs masks; k = random ring
    # WIRE-plane pair-key agreement (comm/keyexchange.py): "dh" (default)
    # negotiates per-pair Diffie-Hellman secrets over the broker so the
    # coordinator cannot unmask any single client; "shared_seed" derives
    # pair keys from the experiment seed (coordinator-trusted — only
    # appropriate when the aggregator is trusted or for broker-less
    # tests).  The ENGINE plane ignores this: a simulation holds every
    # client in one process regardless.
    secure_agg_key_exchange: str = "dh"   # dh | shared_seed
    # Dropout-recovery threshold (privacy/dropout.py): each client
    # Shamir-shares its round secrets across its recovery set (its pairing
    # partners) and reconstruction needs ceil(threshold · set_size)
    # surviving shares.  Higher tolerates fewer dropouts but forces a
    # bigger coalition to break a dead client's masks; 0.5 matches the
    # Bonawitz honest-majority setting.
    secure_agg_threshold: float = 0.5
    # UPLINK update compression on the wire/file planes
    # (fed/compression.py): workers compress their delta before it rides
    # the socket; the coordinator's StreamingFolder folds topk frames
    # sparse-natively (O(k) per contribution, comm/aggregation.py).
    compress: str = "none"            # none | int8 | topk
    # UPLINK error feedback (comm/worker.py): carry the compression
    # residual (delta - decompress(compress(delta))) into the next
    # round's delta before compressing — symmetric to the downlink
    # encoder's reconstruction-base feedback.  Only engages when
    # ``compress`` is lossy; reset on resync/param-cache miss; rejected
    # under secure_agg (masked updates are dense by construction).
    compress_feedback: bool = False
    # Topk keep density (fraction of entries kept per leaf) for the
    # UPLINK codec.  Feedback de-biases sparsification, which makes the
    # density a real accuracy/bytes knob rather than a fixed bias cap.
    topk_fraction: float = 0.05
    # Adaptive per-round topk density (comm/worker.py _adapt_topk): each
    # worker steers its effective fraction off the round-over-round trend
    # of its error-feedback residual norm (growing residual → widen,
    # shrinking → tighten), clipped to [topk_min_fraction,
    # topk_max_fraction].  Requires compress="topk" + compress_feedback
    # (the controller's signal IS the feedback residual).
    topk_adaptive: bool = False
    topk_min_fraction: float = 0.01
    topk_max_fraction: float = 0.25
    # DOWNLINK compression (synchronous coordinator broadcast): ship the
    # server delta through the same codecs against a worker-side param
    # cache (comm/downlink.py).  "none" keeps the broadcast byte-identical
    # to builds without the feature.
    compress_down: str = "none"       # none | int8 | topk
    # Aggregation quorum for the socket coordinators: a round whose
    # completed-update count falls below ceil(fraction * cohort) becomes
    # an explicit no-op (the secure-agg discarded-round convention)
    # instead of silently averaging a couple of survivors.  0 disables —
    # today's behavior, and the default.
    min_cohort_fraction: float = 0.0
    # Rank-r LoRA adapter federation (fed/lora.py): clients train and
    # ship ONLY low-rank factors for the partition-rule-targeted matmul
    # params (uplink O(r·d) instead of O(model)); the server folds
    # factor trees and merges B·A·(alpha/r) into the global model every
    # ``lora_merge_every`` aggregations.  0 disables — round records and
    # wire frames stay byte-identical to builds without the feature.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_merge_every: int = 10
    # Chaos knob for the convergence observatory's divergence gate
    # (scripts/learn_smoke.py): multiply the client lr by
    # ``lr_spike_multiplier`` for exactly round ``lr_spike_round``.
    # The gate is config-static (fed/strategies.lr_scale_for_round), so
    # default graphs — and round records — are byte-identical with the
    # knob off.  -1 disables.
    lr_spike_round: int = -1
    lr_spike_multiplier: float = 1.0


@dataclasses.dataclass(frozen=True)
class RunConfig:
    name: str = "default"
    seed: int = 0
    backend: str = "auto"             # "auto" | "tpu" | "cpu"  (CLI --backend)
    mesh_axis: str = "clients"
    seq_axis: str = "seq"             # SP axis (attn_impl="ring"/"ulysses")
    tp_axis: str = "model"            # tensor/expert-parallel axis (parallel/tp.py)
    tp_size: int = 1                  # model-axis size for from_config meshes
    log_every: int = 1
    eval_every: int = 1
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0         # 0 disables
    # Shard-native streaming checkpoints (ckpt/streaming.py): per-shard
    # CRC-checked files + a manifest commit marker fsynced last, restore
    # re-shards onto the current mesh without full-tree assembly.  False
    # keeps the orbax RoundCheckpointer path byte-identical to before.
    ckpt_stream: bool = False
    profile_dir: Optional[str] = None  # jax.profiler trace output (rounds 1-2)
    trace_dir: Optional[str] = None    # span-trace Chrome JSON output dir
    trace_rounds: int = 0              # trace only the first N rounds (0 = all)
    # --- comm-plane robustness (comm/coordinator.py, comm/worker.py) ----
    evict_after: int = 3               # consecutive failed rounds → evicted
    worker_enroll_timeout: float = 3600.0  # worker await_role budget (s)
    comm_retries: int = 2              # transient-failure retries per request
    comm_backoff_base: float = 0.05    # full-jitter backoff base (s)
    comm_backoff_max: float = 2.0      # backoff cap (s)
    # Aggregator tree (comm/aggregator.py): N real aggregator processes
    # each fold one cohort slice and ship one partial sum to the root.
    # 0 = flat federation (every uplink byte lands on the coordinator).
    num_aggregators: int = 0
    # Bounded-deadline failure detection: an aggregator whose retained
    # heartbeat is older than this is treated as dead at dispatch and its
    # slices re-home to live siblings.
    agg_heartbeat_timeout: float = 5.0
    # Tree-async per-slice fold cadence target (seconds): each buffered
    # aggregator auto-sizes its fold threshold K so one partial ships
    # upstream about this often at the slice's observed arrival rate.
    agg_buffer_interval_s: float = 2.0
    # Device-resident fold (--fold-device, ops/fold_kernel.py): server
    # folds run through the fused batched kernel — in-kernel topk8
    # dequant + weighting + scatter, one compile per model — instead of
    # the per-update host-numpy scatter.  The host path stays the
    # bitwise parity oracle; False keeps it byte-identical to before.
    fold_device: bool = False
    # Per-device health ledger (telemetry/health.py): directory the
    # coordinator/aggregator/fleetsim planes write durable straggler
    # attribution into.  None = plane off, no extra I/O, and round
    # records stay byte-identical to the pre-health format.
    health_dir: Optional[str] = None
    # Deterministic fault injection (faults/): path to a FaultPlan JSON
    # installed as the transport interposer; None = no fault layer at all.
    fault_plan: Optional[str] = None
    fault_seed: int = 0
    # Convergence observatory (telemetry/convergence.py): stamp conv_*
    # learning-health keys on round records and export learn.* metrics.
    # Off by default — default round records stay byte-identical (pinned
    # by tests on the sync, async, and fleetsim planes).
    learn_observe: bool = False


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    fed: FedConfig = dataclasses.field(default_factory=FedConfig)
    run: RunConfig = dataclasses.field(default_factory=RunConfig)

    def replace(self, **sections) -> "ExperimentConfig":
        return dataclasses.replace(self, **sections)


def _cfg(**kw) -> ExperimentConfig:
    return ExperimentConfig(**kw)


# The five driver benchmark configs (BASELINE.json "configs", quoted in
# BASELINE.md).  Model scale knobs follow the named architectures; dataset
# shapes come from data/registry.py.
CONFIGS: dict[str, ExperimentConfig] = {
    # 1. "FedAvg 2-layer MLP on MNIST, 10 simulated clients (CPU baseline)"
    "mnist_mlp_fedavg": _cfg(
        data=DataConfig(dataset="mnist", num_clients=10, partition="iid"),
        model=ModelConfig(name="mlp", num_classes=10, hidden_dim=200, depth=2),
        fed=FedConfig(strategy="fedavg", rounds=20, local_epochs=1,
                      batch_size=32, lr=0.1, momentum=0.9),
        run=RunConfig(name="mnist_mlp_fedavg"),
    ),
    # 2. "FedAvg CNN on CIFAR-10, 100 non-IID clients (Dirichlet α=0.5)"
    "cifar10_cnn_fedavg": _cfg(
        data=DataConfig(dataset="cifar10", num_clients=100,
                        partition="dirichlet", dirichlet_alpha=0.5),
        model=ModelConfig(name="cnn", num_classes=10, width=64,
                          dtype="bfloat16"),
        fed=FedConfig(strategy="fedavg", rounds=100, cohort_size=20,
                      local_epochs=1, batch_size=32, lr=0.05, momentum=0.9),
        run=RunConfig(name="cifar10_cnn_fedavg"),
    ),
    # 3. "FedProx ResNet-18 on CIFAR-100, 100 clients, μ=0.01"
    "cifar100_resnet18_fedprox": _cfg(
        data=DataConfig(dataset="cifar100", num_clients=100,
                        partition="dirichlet", dirichlet_alpha=0.5),
        model=ModelConfig(name="resnet18", num_classes=100,
                          dtype="bfloat16"),
        fed=FedConfig(strategy="fedprox", prox_mu=0.01, rounds=100,
                      cohort_size=20, local_epochs=1, batch_size=32,
                      lr=0.05, momentum=0.9),
        run=RunConfig(name="cifar100_resnet18_fedprox"),
    ),
    # 4. "FedAvg BERT-base on AG-News, 50 text clients"
    "agnews_bert_fedavg": _cfg(
        data=DataConfig(dataset="agnews", num_clients=50, partition="iid"),
        model=ModelConfig(name="bert", num_classes=4, width=768, depth=12,
                          num_heads=12, seq_len=128, dtype="bfloat16"),
        fed=FedConfig(strategy="fedavg", rounds=50, cohort_size=10,
                      local_epochs=1, batch_size=16, lr=5e-5, momentum=0.0,
                      local_optimizer="adam",
                      lr_schedule="warmup_cosine", warmup_rounds=5,
                      lr_min_fraction=0.1),
        run=RunConfig(name="agnews_bert_fedavg"),
    ),
    # 5. "Cross-silo ViT-B/16 on FEMNIST, 3400 clients → v5e-256"
    "femnist_vit_cross_silo": _cfg(
        data=DataConfig(dataset="femnist", num_clients=3400,
                        partition="dirichlet", dirichlet_alpha=0.3),
        model=ModelConfig(name="vit_b16", num_classes=62, width=768,
                          depth=12, num_heads=12, patch_size=16,
                          dtype="bfloat16"),
        fed=FedConfig(strategy="fedavg", rounds=100, cohort_size=256,
                      local_epochs=1, batch_size=16, lr=0.03, momentum=0.9,
                      lr_schedule="warmup_cosine", warmup_rounds=5,
                      lr_min_fraction=0.05),
        run=RunConfig(name="femnist_vit_cross_silo"),
    ),
}


# Thematic parity config beyond the five BASELINE entries: the
# reference's ACTUAL deployment task — IoT network-anomaly detection on
# edge devices (SURVEY.md §0) — as a federated TCN over traffic windows.
CONFIGS["iot_traffic_tcn_fedavg"] = _cfg(
    data=DataConfig(dataset="iot_traffic", num_clients=50,
                    partition="dirichlet", dirichlet_alpha=0.3),
    model=ModelConfig(name="tcn", num_classes=8, width=64, depth=4,
                      dtype="bfloat16"),
    fed=FedConfig(strategy="fedavg", rounds=50, cohort_size=10,
                  local_epochs=1, batch_size=32, lr=0.05, momentum=0.9),
    run=RunConfig(name="iot_traffic_tcn_fedavg"),
)


# A causal byte-level language model (ROADMAP M1/M4): EvaByte at its
# published widths, 4 of its 32 layers and half its context, which is what
# one 16 GB chip holds at cohort 1 with SGD clients (PERF.md section 4).
# One example is one sequence of 16,384 bytes with 8 next-byte labels a
# position (dataset ``bytes``).
CONFIGS["evabyte_fedavg"] = _cfg(
    data=DataConfig(dataset="bytes", num_clients=8, partition="iid"),
    model=ModelConfig(name="evabyte", num_classes=320, vocab_size=320,
                      width=4096, depth=4, num_heads=32, seq_len=16384,
                      ffn_dim=11008, window_size=2048, chunk_size=16,
                      num_pred_heads=8, rope_theta=100000.0,
                      dtype="bfloat16", attn_impl="flash", remat=True),
    fed=FedConfig(strategy="fedavg", rounds=20, cohort_size=1,
                  local_steps=2, batch_size=1, lr=0.1, momentum=0.0),
    run=RunConfig(name="evabyte_fedavg", eval_every=2),
)


# A hybrid state-space / sparse language model: Nemotron-3-Super at its
# published widths as one chip's share of a stated deployment (the first 11
# of 88 layers, a pipeline stage; 8 of 512 experts, a quarter of the mixers'
# heads, an eighth of the vocabulary: PERF.md section 4).  One example is
# one sequence of 16,384 tokens with the next token as each position's
# label (dataset ``tokens``).
CONFIGS["nemotron_h_fedavg"] = _cfg(
    data=DataConfig(dataset="tokens", num_clients=8, partition="iid"),
    model=ModelConfig(name="nemotron_h", num_classes=16384, vocab_size=16384,
                      width=4096, seq_len=16384, layer_pattern="MEMEMEM*EME",
                      chunk_size=128, num_experts=512, num_heads=8,
                      num_kv_heads=1, head_dim=128,
                      dtype="bfloat16", attn_impl="flash", remat=True),
    fed=FedConfig(strategy="fedavg", rounds=20, cohort_size=1,
                  local_steps=2, batch_size=1, lr=0.003, momentum=0.0),
    run=RunConfig(name="nemotron_h_fedavg", eval_every=2),
)


# A sparse latent-attention language model on a widened residual path:
# Xing4.0-29B-A4B at its published widths as one chip's share of a stated
# deployment (5 of 40 layers, 1 of them dense; 8 of 64 experts and an
# eighth of the vocabulary, attention whole; without the prediction module,
# which the parity probe has no room for: PERF.md section 4).  One example
# is one sequence of 8,192 tokens with the token after each position as
# its label (dataset ``tokens_ahead`` at a horizon of 1).
CONFIGS["xing4_fedavg"] = _cfg(
    data=DataConfig(dataset="tokens_ahead", num_clients=8, partition="iid"),
    model=ModelConfig(name="xing4", num_classes=16384, vocab_size=16384,
                      width=3584, depth=5, num_heads=32, seq_len=8192,
                      ffn_dim=9216, rope_theta=10000.0, num_experts=64,
                      experts_per_token=4, expert_dim=1024,
                      shared_expert_dim=1024, routed_scale=2.0, mtp_modules=0,
                      dtype="bfloat16", attn_impl="flash", remat=True),
    fed=FedConfig(strategy="fedavg", rounds=20, cohort_size=1,
                  local_steps=2, batch_size=1, lr=0.003, momentum=0.0),
    run=RunConfig(name="xing4_fedavg", eval_every=2),
)


# A hybrid linear-attention / latent-attention sparse language model:
# Ling-3.0-flash's language model at its published widths as one chip's
# share of a stated deployment (published layers 1-7 of 42: one dense layer,
# one whole period of five delta-rule layers to one of latent attention; 8
# of 512 experts chosen within 4 of 8 groups, an eighth of the vocabulary:
# PERF.md section 4).  One example is one sequence of 4,096 tokens with the
# next token as each position's label (dataset ``tokens_4k``).
CONFIGS["ling3_fedavg"] = _cfg(
    data=DataConfig(dataset="tokens_4k", num_clients=8, partition="iid"),
    model=ModelConfig(name="ling3", num_classes=19648, vocab_size=19648,
                      width=2560, depth=7, first_layer=1, layer_group_size=6,
                      dense_layers=1, num_heads=32, head_dim=128,
                      seq_len=4096, conv_kernel=4, chunk_size=64,
                      kda_lower_bound=-5.0, kv_rank=512, nope_dim=128,
                      rope_dim=64, v_dim=128, rope_theta=6e6, ffn_dim=6144,
                      num_experts=512, experts_per_token=8, expert_groups=8,
                      expert_groups_kept=4, expert_dim=768,
                      shared_expert_dim=768, routed_scale=2.5,
                      moe_token_block=2048, moe_row_tile=1024,
                      dtype="bfloat16",
                      attn_impl="flash", remat=True),
    fed=FedConfig(strategy="fedavg", rounds=20, cohort_size=1,
                  local_steps=2, batch_size=1, lr=0.01, momentum=0.0),
    run=RunConfig(name="ling3_fedavg", eval_every=2),
)


def get_config(name: str) -> ExperimentConfig:
    if name not in CONFIGS:
        raise KeyError(f"unknown config {name!r}; available: {sorted(CONFIGS)}")
    return CONFIGS[name]
