"""Model registry: config name → flax module + init helper."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from colearn_federated_learning_tpu.utils.config import ModelConfig


# Families whose blocks can be rematerialised, and those of them that run
# inside ``shard_map`` on a shard of the sequence.
REMAT_FAMILIES = ("bert", "moe_bert", "vit_b16", "evabyte", "nemotron_h",
                  "xing4", "ling3")
SEQ_PARALLEL_FAMILIES = ("bert", "moe_bert")


def _dtype(cfg: ModelConfig):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[cfg.dtype]


def build_model(cfg: ModelConfig, seq_axis_name: str | None = None):
    """Return the flax module for a ModelConfig.

    ``seq_axis_name``: mesh axis for sequence parallelism — only meaningful
    for text models with ``attn_impl="ring"``, which must then be applied
    inside ``shard_map`` with the sequence dim sharded over that axis.
    """
    dtype = _dtype(cfg)
    if seq_axis_name is not None and cfg.name not in SEQ_PARALLEL_FAMILIES:
        raise ValueError(
            "sequence parallelism is only supported for "
            f"{'/'.join(SEQ_PARALLEL_FAMILIES)} (their attention runs over "
            f"the axis), not {cfg.name!r}"
        )
    if cfg.remat and cfg.name not in REMAT_FAMILIES:
        raise ValueError(
            "remat is only implemented for the transformer families "
            f"({'/'.join(REMAT_FAMILIES)}), not {cfg.name!r} — silently "
            "ignoring it would fake the memory savings"
        )
    if cfg.name == "mlp":
        from colearn_federated_learning_tpu.models.mlp import MLP

        return MLP(num_classes=cfg.num_classes, hidden_dim=cfg.hidden_dim,
                   depth=cfg.depth, dtype=dtype)
    if cfg.name == "cnn":
        from colearn_federated_learning_tpu.models.cnn import CNN

        return CNN(num_classes=cfg.num_classes, width=cfg.width, dtype=dtype,
                   stem=cfg.stem, norm=cfg.norm)
    if cfg.name == "resnet18":
        from colearn_federated_learning_tpu.models.resnet import ResNet18

        return ResNet18(num_classes=cfg.num_classes, width=cfg.width, dtype=dtype)
    if cfg.name == "bert":
        from colearn_federated_learning_tpu.models.bert import BertClassifier

        return BertClassifier(num_classes=cfg.num_classes, vocab_size=cfg.vocab_size,
                              embed_dim=cfg.width, depth=cfg.depth,
                              num_heads=cfg.num_heads, max_len=cfg.seq_len,
                              dtype=dtype, attn_impl=cfg.attn_impl,
                              seq_axis_name=seq_axis_name, remat=cfg.remat)
    if cfg.name == "moe_bert":
        from colearn_federated_learning_tpu.models.bert import BertClassifier

        # Same encoder as "bert" with MoE FFN blocks interleaved
        # (models/moe.py; expert banks shard over the model axis).
        return BertClassifier(num_classes=cfg.num_classes,
                              vocab_size=cfg.vocab_size, embed_dim=cfg.width,
                              depth=cfg.depth, num_heads=cfg.num_heads,
                              max_len=cfg.seq_len, dtype=dtype,
                              attn_impl=cfg.attn_impl,
                              seq_axis_name=seq_axis_name,
                              num_experts=cfg.num_experts, remat=cfg.remat)
    if cfg.name == "tcn":
        from colearn_federated_learning_tpu.models.tcn import TCN

        return TCN(num_classes=cfg.num_classes, width=cfg.width,
                   depth=cfg.depth, dtype=dtype)
    if cfg.name == "vit_b16":
        from colearn_federated_learning_tpu.models.vit import ViT

        return ViT(num_classes=cfg.num_classes, embed_dim=cfg.width,
                   depth=cfg.depth, num_heads=cfg.num_heads,
                   patch_size=cfg.patch_size, dtype=dtype,
                   attn_impl=cfg.attn_impl, remat=cfg.remat)
    if cfg.name == "evabyte":
        from colearn_federated_learning_tpu.models.evabyte import EvaByte
        from colearn_federated_learning_tpu.ops.eva import EVA_IMPLS

        if cfg.attn_impl not in EVA_IMPLS:
            raise ValueError(
                f"evabyte's attention runs as {EVA_IMPLS}, not "
                f"{cfg.attn_impl!r}")
        return EvaByte(vocab_size=cfg.vocab_size, embed_dim=cfg.width,
                       depth=cfg.depth, num_heads=cfg.num_heads,
                       ffn_dim=cfg.ffn_dim, window=cfg.window_size,
                       chunk=cfg.chunk_size,
                       num_pred_heads=cfg.num_pred_heads,
                       rope_theta=cfg.rope_theta, dtype=dtype,
                       attn_impl=cfg.attn_impl, remat=cfg.remat)
    if cfg.name == "nemotron_h":
        from colearn_federated_learning_tpu.models.nemotron_h import NemotronH

        if cfg.attn_impl not in ("flash", "dense"):
            raise ValueError(
                "nemotron_h's attention runs as ('flash', 'dense') on one "
                f"device, not {cfg.attn_impl!r}")
        return NemotronH(
            pattern=cfg.layer_pattern, vocab_size=cfg.vocab_size,
            embed_dim=cfg.width, mamba_heads=cfg.mamba_heads,
            mamba_head_dim=cfg.mamba_head_dim,
            mamba_groups=cfg.mamba_groups, state_size=cfg.ssm_state_size,
            conv_kernel=cfg.conv_kernel, chunk=cfg.chunk_size,
            experts_total=cfg.num_experts,
            experts_held=(cfg.experts_first, cfg.experts_held),
            top_k=cfg.experts_per_token, latent_dim=cfg.latent_dim,
            expert_dim=cfg.expert_dim, shared_dim=cfg.shared_expert_dim,
            routed_scale=cfg.routed_scale, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads or cfg.num_heads,
            head_dim=cfg.head_dim or cfg.width // cfg.num_heads,
            dtype=dtype, attn_impl=cfg.attn_impl, remat=cfg.remat)
    if cfg.name == "xing4":
        from colearn_federated_learning_tpu.models.mla import MLA_IMPLS
        from colearn_federated_learning_tpu.models.xing4 import Xing4

        if cfg.attn_impl not in MLA_IMPLS:
            raise ValueError(
                f"xing4's attention runs as {MLA_IMPLS} on one device, not "
                f"{cfg.attn_impl!r}")
        return Xing4(
            vocab_size=cfg.vocab_size, embed_dim=cfg.width, depth=cfg.depth,
            dense_layers=cfg.dense_layers, streams=cfg.hc_streams,
            sinkhorn_iters=cfg.sinkhorn_iters,
            sinkhorn_eps=cfg.sinkhorn_eps,
            res_clamp=(cfg.res_clamp_min, cfg.res_clamp_max),
            num_heads=cfg.num_heads, q_rank=cfg.q_rank, kv_rank=cfg.kv_rank,
            nope_dim=cfg.nope_dim, rope_dim=cfg.rope_dim, v_dim=cfg.v_dim,
            rope_theta=cfg.rope_theta,
            yarn=(cfg.yarn_factor, cfg.yarn_original_max, cfg.yarn_beta_fast,
                  cfg.yarn_beta_slow, cfg.yarn_mscale_all_dim),
            ffn_dim=cfg.ffn_dim, experts_total=cfg.num_experts,
            experts_held=(cfg.experts_first, cfg.experts_held),
            top_k=cfg.experts_per_token, expert_dim=cfg.expert_dim,
            shared_dim=cfg.shared_expert_dim, routed_scale=cfg.routed_scale,
            mtp_modules=cfg.mtp_modules, norm_eps=cfg.norm_eps, dtype=dtype,
            attn_impl=cfg.attn_impl, remat=cfg.remat)
    if cfg.name == "ling3":
        from colearn_federated_learning_tpu.models.ling3 import Ling3
        from colearn_federated_learning_tpu.models.mla import MLA_IMPLS

        if cfg.attn_impl not in MLA_IMPLS:
            raise ValueError(
                f"ling3's attention runs as {MLA_IMPLS} on one device, not "
                f"{cfg.attn_impl!r}")
        return Ling3(
            vocab_size=cfg.vocab_size, embed_dim=cfg.width, depth=cfg.depth,
            first_layer=cfg.first_layer,
            layer_group_size=cfg.layer_group_size,
            dense_layers=cfg.dense_layers, num_heads=cfg.num_heads,
            head_dim=cfg.head_dim or cfg.width // cfg.num_heads,
            conv_kernel=cfg.conv_kernel, chunk=cfg.chunk_size,
            lower_bound=cfg.kda_lower_bound, kv_rank=cfg.kv_rank,
            nope_dim=cfg.nope_dim, rope_dim=cfg.rope_dim, v_dim=cfg.v_dim,
            rope_theta=cfg.rope_theta, ffn_dim=cfg.ffn_dim,
            experts_total=cfg.num_experts,
            experts_held=(cfg.experts_first, cfg.experts_held),
            top_k=cfg.experts_per_token, n_group=cfg.expert_groups,
            topk_group=cfg.expert_groups_kept, expert_dim=cfg.expert_dim,
            shared_dim=cfg.shared_expert_dim, routed_scale=cfg.routed_scale,
            token_block=cfg.moe_token_block, row_tile=cfg.moe_row_tile,
            expert_limits=tuple(cfg.expert_limits),
            shared_limits=tuple(cfg.shared_expert_limits),
            norm_eps=cfg.norm_eps, dtype=dtype, attn_impl=cfg.attn_impl,
            remat=cfg.remat)
    raise KeyError(f"unknown model {cfg.name!r}")


def init_params(model, example_x, key: jax.Array):
    """Initialize float32 parameters for one example batch."""
    variables = model.init(key, example_x, train=False)
    if set(variables.keys()) != {"params"}:
        raise ValueError(
            f"model carries non-param collections {sorted(variables.keys())}; "
            "federated local training requires pure-param models "
            "(use GroupNorm/LayerNorm, not BatchNorm)"
        )
    return variables["params"]
