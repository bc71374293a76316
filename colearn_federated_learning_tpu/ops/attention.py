"""Flash attention as a Pallas TPU kernel.

Blockwise attention with online-softmax accumulation: each grid step owns
one (batch·head, q-block) tile, keeps K/V VMEM-resident, and loops over
k-blocks with running (max, normaliser, accumulator) carries — the (L, L)
score matrix never exists in HBM, and the two matmuls per block land on the
MXU.  The same rescaling recurrence runs ACROSS devices in
parallel/ring.py; composing the two (ring outside, flash inside each block)
is the standard long-context stack.

Gradients: ``flash_attention`` carries a ``jax.custom_vjp`` whose backward
pass is ALSO blockwise Pallas (FlashAttention-2 recurrence): the forward
additionally emits the per-row logsumexp, and two kernels recompute
probabilities tile-by-tile — one accumulating dQ over k-blocks, one
accumulating dK/dV over q-blocks — so the (L, L) score matrix never exists
in either direction.

On CPU (the virtual-mesh test platform) the kernel runs in Pallas interpret
mode automatically.

The reference has no kernel layer at all (SURVEY.md §1: "no custom kernel
layer"); this is TPU-native capability the rebuild adds for the BERT/ViT
federated configs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from colearn_federated_learning_tpu import telemetry

_NEG = -1e30
# What the forward leaves for the backward besides its own arguments, by the
# names a ``jax.checkpoint`` policy may ask for (``save_only_these_names``):
# a rematerialised block that keeps both does not run ``flash_fwd`` again.
# Under any other policy, and outside a checkpoint, a name is the identity.
FLASH_RESIDUAL_NAMES = ("flash_out", "flash_lse")


@functools.lru_cache(maxsize=None)
def _tpu_generation() -> int:
    """TPU generation of the default backend's first device; 0 when the
    default backend is not a TPU (the kernels then run interpreted).
    Drives the VMEM cap and block-size defaults: v4/v5/v6 carry ≥128 MB
    physical VMEM, v2/v3 far less.  A TPU whose ``device_kind`` names no
    generation is an error: guessing would quietly hand it the small
    configuration."""
    import re

    if jax.default_backend() != "tpu":
        return 0
    kind = jax.devices()[0].device_kind
    m = re.search(r"v(\d+)", kind.lower())
    if m is None:
        raise RuntimeError(
            f"cannot read a TPU generation from device_kind {kind!r}; "
            "ops/attention.py sizes its blocks and VMEM cap by generation"
        )
    return int(m.group(1))


def _default_block() -> int:
    """512 on v4+ (and in interpret mode, where it only shortens the Python
    loop); 128 on v2/v3, because the 512 configuration needs the raised
    VMEM cap that ``_tpu_params`` only grants to v4+ hardware."""
    return 128 if 0 < _tpu_generation() < 4 else 512


def _tpu_params():
    """Mosaic compiler params for the non-interpret (real TPU) path: the
    default 16 MB scoped-vmem cap rejects the fast 512-block configuration
    beyond L≈4k; v4/v5/v6 have ≥128 MB physical VMEM, so raise the cap and
    let the (bq, bk) f32 score tiles + whole-row K/V residency fit
    (measured on v5e: L=32k fwd+bwd needs ~100 MB of scoped buffers).  On
    older generations (v2/v3) the raised cap itself would fail Mosaic
    compilation — keep the conservative 16 MB default there."""
    from jax.experimental.pallas import tpu as pltpu

    if _tpu_generation() >= 4:
        return pltpu.CompilerParams(vmem_limit_bytes=112 * 1024 * 1024)
    return None


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _shifted(q_end, prefix: int):
    """Keys a causal query block ending at ``q_end`` reaches; with no
    prefix the expression is the one it always was."""
    return q_end + prefix if prefix else q_end


def _causal(s, qb, kb, block_q: int, block_k: int, prefix: int):
    """Scores of one (q-block, k-block) tile with the keys a causal query
    may not see set to ``_NEG``.  The first ``prefix`` keys stand before
    position 0 (every query sees them; only the key bias masks them) and
    the causal test runs on the keys after them."""
    q_pos = qb * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if prefix:
        k_pos = k_pos - prefix
    return jnp.where(q_pos >= k_pos, s, _NEG)


def _flash_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                  block_k: int, scale: float, causal: bool, block_q: int,
                  prefix: int = 0):
    """One (batch·head, q-block) tile; K/V for the whole row are VMEM-resident.

    q_ref: (1, block_q, D) — this tile's queries
    k_ref: (1, Lk, D), v_ref: (1, Lk, Dv) — all keys/values for this
      batch·head; the values may have a width of their own
    bias_ref: (1, Lk, 1) — additive key bias (0 valid / _NEG masked).  The
      sequence dim sits on the SUBLANE axis with a singleton lane dim:
      Mosaic requires a block's lane dim be 128-divisible or span the whole
      array, and in-kernel dynamic slices must be lane-aligned — k-block
      offsets are only 8-aligned, which the sublane axis accepts.
    o_ref: (1, block_q, Dv)
    lse_ref: (1, block_q, 1) — per-row logsumexp, the backward residual
    """
    Lk = k_ref.shape[1]
    Dv = v_ref.shape[2]
    num_kb = Lk // block_k
    qb = pl.program_id(1)

    # Keep the model dtype (bf16 on TPU) INTO the dots: the MXU runs
    # bf16×bf16→f32 at full rate, while f32×f32 costs ~4× — casting up
    # front would throw away most of the kernel's throughput.  All
    # accumulation (m/l/acc, softmax math) stays float32.
    q = q_ref[0]                                             # (bq, D)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)
        s = s * scale + bias_ref[0, pl.ds(kb * block_k, block_k), 0][None, :]
        if causal:
            s = _causal(s, qb, kb, block_q, block_k, prefix)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # Fully-masked blocks: m_new sits at the _NEG floor and exp(0)=1
        # would leak padding; zero those entries (same fix as ring.py).
        p = jnp.where(s > 0.5 * _NEG, p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        acc_new = acc * corr + lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    if causal:
        # Skip k-blocks entirely above the diagonal.
        num_kb = jnp.minimum(
            num_kb, pl.cdiv(_shifted((qb + 1) * block_q, prefix), block_k))
    m0 = jnp.full((q.shape[0], 1), _NEG, jnp.float32)
    l0 = jnp.zeros((q.shape[0], 1), jnp.float32)
    acc0 = jnp.zeros((q.shape[0], Dv), jnp.float32)
    m, l, acc = lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
    # Fully-masked rows (l == 0) get lse = +BIG so the backward's
    # exp(s - lse) recomputation yields exactly-zero probabilities there.
    lse = jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), -_NEG)
    lse_ref[0] = lse


def _blocks(q, k, v, kv_mask, block_q, block_k, interpret):
    """Shared fwd/bwd plumbing: row-major (B·H, L, D) views padded to block
    multiples, the additive key bias, and resolved block sizes."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # Which of the two ran is not visible in the result, so count it (at
    # trace time): a chip run that traced an interpreted kernel is a fault.
    telemetry.get_registry().counter(
        "ops.flash_trace_total",
        labels={"mode": "interpret" if interpret else "mosaic"}).inc()
    if block_q is None:
        block_q = _default_block()
    if block_k is None:
        block_k = _default_block()

    bq = min(block_q, _round_up(Lq, 8))
    bk = min(block_k, _round_up(Lk, 8))
    Lq_p, Lk_p = _round_up(Lq, bq), _round_up(Lk, bk)

    # (B, L, H, D) -> (B*H, L, D) rows; pad sequence to block multiples.
    def to_rows(a, L_p):
        a = jnp.pad(a, ((0, 0), (0, L_p - a.shape[1]), (0, 0), (0, 0)))
        return a.transpose(0, 2, 1, 3).reshape(B * H, L_p, a.shape[-1])

    if kv_mask is None:
        bias = jnp.zeros((B, Lk), jnp.float32)
    else:
        bias = jnp.where(kv_mask, 0.0, _NEG).astype(jnp.float32)
    bias = jnp.pad(bias, ((0, 0), (0, Lk_p - Lk)), constant_values=_NEG)
    bias = bias[:, :, None]                                   # (B, Lk_p, 1)
    return (B, Lq, H, D, Lk, bq, bk, Lq_p, Lk_p, to_rows, bias, interpret)


def _scale(scale: Optional[float], D: int) -> float:
    """The factor on the scores: ``D ** -0.5`` unless the caller has its
    own (latent attention under yarn multiplies it)."""
    return 1.0 / (D ** 0.5) if scale is None else scale


def _flash_impl(q, k, v, kv_mask, causal: bool,
                block_q: int, block_k: int, interpret: Optional[bool],
                return_lse: bool = False, prefix: int = 0,
                scale: Optional[float] = None):
    (B, Lq, H, D, Lk, bq, bk, Lq_p, Lk_p, to_rows, bias,
     interpret) = _blocks(q, k, v, kv_mask, block_q, block_k, interpret)
    qr, kr, vr = to_rows(q, Lq_p), to_rows(k, Lk_p), to_rows(v, Lk_p)
    Dv = v.shape[-1]

    kernel = functools.partial(
        _flash_kernel, block_k=bk, scale=_scale(scale, D),
        causal=causal, block_q=bq, prefix=prefix,
    )
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(B * H, Lq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Lk_p, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk_p, Dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk_p, 1), lambda b, i: (b // H, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lq_p, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, Lq_p, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=None if interpret else _tpu_params(),
    )(qr, kr, vr, bias)
    out = out.reshape(B, H, Lq_p, Dv).transpose(0, 2, 1, 3)[:, :Lq]
    if return_lse:
        return out, lse                                    # lse stays padded
    return out


def _flash_dq_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, *, block_k: int, scale: float,
                     causal: bool, block_q: int, prefix: int = 0):
    """dQ for one (batch·head, q-block) tile, looping over k-blocks:
    p = exp(qk^T·s + bias − lse);  ds = p ⊙ (dO·V^T − Δ);  dq += ds·K·s."""
    Lk = k_ref.shape[1]
    num_kb = Lk // block_k
    qb = pl.program_id(1)

    # Model-dtype (bf16) operands into every dot, f32 accumulation out —
    # see _flash_kernel.  The softmax scale folds into s post-dot.
    q = q_ref[0]                                             # (bq, D)
    do = do_ref[0]                                           # (bq, D)
    lse = lse_ref[0]                                         # (bq, 1)
    delta = delta_ref[0]                                     # (bq, 1)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s * scale + bias_ref[0, pl.ds(kb * block_k, block_k), 0][None, :]
        if causal:
            s = _causal(s, qb, kb, block_q, block_k, prefix)
        p = jnp.exp(s - lse)                                 # exact softmax
        p = jnp.where(s > 0.5 * _NEG, p, 0.0)
        dp = lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        num_kb = jnp.minimum(
            num_kb, pl.cdiv(_shifted((qb + 1) * block_q, prefix), block_k))
    dq = lax.fori_loop(
        0, num_kb, body, jnp.zeros((q.shape[0], q.shape[1]), jnp.float32)
    )
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref,
                      delta_ref, dk_ref, dv_ref, *, block_q: int,
                      scale: float, causal: bool, block_k: int,
                      prefix: int = 0):
    """dK/dV for one (batch·head, k-block) tile, looping over q-blocks:
    dv += p^T·dO;  dk += ds^T·(q·s)."""
    Lq = q_ref.shape[1]
    num_qb = Lq // block_q
    kb = pl.program_id(1)

    # Model-dtype (bf16) operands into every dot, f32 accumulation out —
    # see _flash_kernel.  The softmax scale is applied to s post-dot and
    # folded into dk once at the end (dk = scale · Σ ds^T q).
    k_blk = k_ref[0]                                         # (bk, D)
    v_blk = v_ref[0]
    bias = bias_ref[0, :, 0][None, :]                        # (1, bk)

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]    # (bq, 1)
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (bq, bk)
        s = s * scale + bias
        if causal:
            s = _causal(s, qb, kb, block_q, block_k, prefix)
        p = jnp.exp(s - lse)
        p = jnp.where(s > 0.5 * _NEG, p, 0.0)
        dv_new = dv + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

    qb0 = 0
    if causal:
        # q-blocks strictly above the diagonal contribute nothing.
        qb0 = (kb * block_k) // block_q
        if prefix:
            qb0 = jnp.maximum(kb * block_k - prefix, 0) // block_q
    dk, dv = lax.fori_loop(
        qb0, num_qb, body,
        (jnp.zeros(k_blk.shape, jnp.float32),
         jnp.zeros(v_blk.shape, jnp.float32)),
    )
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, kv_mask, out, lse, g, causal,
                    block_q, block_k, interpret, prefix: int = 0,
                    scale: Optional[float] = None):
    (B, Lq, H, D, Lk, bq, bk, Lq_p, Lk_p, to_rows, bias,
     interpret) = _blocks(q, k, v, kv_mask, block_q, block_k, interpret)
    qr, kr, vr = to_rows(q, Lq_p), to_rows(k, Lk_p), to_rows(v, Lk_p)
    gr = to_rows(g, Lq_p)
    # Δ = rowsum(dO ⊙ O): tiny, batched — plain XLA, not worth a kernel.
    # Padded query rows have g = 0, so their Δ and ds vanish.
    outr = to_rows(out, Lq_p)
    delta = jnp.sum(gr.astype(jnp.float32) * outr.astype(jnp.float32),
                    axis=-1)[:, :, None]                     # (B·H, Lq_p, 1)

    scale = _scale(scale, D)
    Dv = v.shape[-1]
    dq_kernel = functools.partial(_flash_dq_kernel, block_k=bk, scale=scale,
                                  causal=causal, block_q=bq, prefix=prefix)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_dq",
        grid=(B * H, Lq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Lk_p, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk_p, Dv), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk_p, 1), lambda b, i: (b // H, 0, 0)),
            pl.BlockSpec((1, bq, Dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Lq_p, D), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else _tpu_params(),
    )(qr, kr, vr, bias, gr, lse, delta)

    dkv_kernel = functools.partial(_flash_dkv_kernel, block_q=bq, scale=scale,
                                   causal=causal, block_k=bk, prefix=prefix)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_dkv",
        grid=(B * H, Lk_p // bk),
        in_specs=[
            pl.BlockSpec((1, Lq_p, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, 1), lambda b, j: (b // H, j, 0)),
            pl.BlockSpec((1, Lq_p, Dv), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Lq_p, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Lq_p, 1), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Lk_p, Dv), v.dtype),
        ],
        interpret=interpret,
        compiler_params=None if interpret else _tpu_params(),
    )(qr, kr, vr, bias, gr, lse, delta)

    def from_rows(a, L, L_p):
        return a.reshape(B, H, L_p, a.shape[-1]).transpose(0, 2, 1, 3)[:, :L]

    return (from_rows(dq, Lq, Lq_p), from_rows(dk, Lk, Lk_p),
            from_rows(dv, Lk, Lk_p))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, kv_mask, causal, block_q, block_k, interpret, prefix,
           scale):
    return _flash_impl(q, k, v, kv_mask, causal, block_q, block_k, interpret,
                       prefix=prefix, scale=scale)


def _flash_fwd(q, k, v, kv_mask, causal, block_q, block_k, interpret,
               prefix, scale):
    out, lse = _flash_impl(q, k, v, kv_mask, causal, block_q, block_k,
                           interpret, return_lse=True, prefix=prefix,
                           scale=scale)
    # The log-sum is held dense, (B·H, Lq_p): as the kernel writes it, its
    # singleton lane dimension is padded to a 128-lane tile on the chip, and
    # a kept copy would take 128 × the room.
    out_name, lse_name = FLASH_RESIDUAL_NAMES
    out = checkpoint_name(out, out_name)
    lse = checkpoint_name(lse[..., 0], lse_name)
    return out, (q, k, v, kv_mask, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, prefix, scale, res, g):
    # Blockwise Pallas backward (FlashAttention-2): probabilities are
    # recomputed tile-by-tile from the saved logsumexp — exact gradients,
    # no (L, L) matrix in either direction.
    q, k, v, kv_mask, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, kv_mask, out, lse[..., None], g,
                                 causal, block_q, block_k, interpret, prefix,
                                 scale)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_mask: Optional[jax.Array] = None,
    *,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    prefix: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blockwise (flash) attention over ``(B, L, H, D)`` tensors.

    ``kv_mask``: optional ``(B, L_k)`` bool, False = padding key.  Fully
    masked query rows return 0, matching ``dense_attention``.
    ``prefix`` (static, with ``causal``): the first ``prefix`` keys stand
    before the sequence — every query sees them unless ``kv_mask`` hides
    them — and query ``i`` sees key ``prefix + j`` for ``j <= i``
    (``L_k = prefix + L_q``).  What ``ops/eva.py`` puts there are the
    chunk summaries of earlier windows.
    ``k`` and ``v`` may have fewer heads than ``q`` (grouped-query
    attention: ``H_q`` a multiple of ``H_kv``); query head ``h`` reads
    key/value head ``h // (H_q // H_kv)``.
    ``v`` may have a width of its own (latent attention scores over 192
    and weighs values of 128): the result, its gradient and the kept
    ``flash_out`` have the values' width.  ``scale`` (static) multiplies
    the scores; ``None`` is ``D ** -0.5`` of the queries' width.
    ``interpret=None`` auto-selects Pallas interpret mode off-TPU.
    ``block_q``/``block_k`` default per TPU generation (512 on v4+, 128 on
    v2/v3 whose smaller VMEM rejects the large configuration).
    """
    if prefix:
        if not causal or prefix < 0:
            raise ValueError(
                f"prefix={prefix} needs causal=True and prefix >= 0: without "
                "a causal part every key is a prefix key already")
        # Counted at trace time, as ``ops.flash_trace_total`` is.
        telemetry.get_registry().counter("attention.flash_prefix_calls").inc()
    if k.shape[2] != q.shape[2]:
        heads, kv_heads = q.shape[2], k.shape[2]
        if heads % kv_heads or v.shape[2] != kv_heads:
            raise ValueError(
                f"{heads} query heads do not share {kv_heads} key heads and "
                f"{v.shape[2]} value heads evenly")
        # Every query head is handed its own copy of the head it shares;
        # the copies' gradients sum in the repeat's transpose.
        k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    if k.shape[-1] != q.shape[-1]:
        raise ValueError(
            f"queries of width {q.shape[-1]} cannot score keys of width "
            f"{k.shape[-1]}")
    return _flash(q, k, v, kv_mask, causal, block_q, block_k, interpret,
                  prefix, scale)
