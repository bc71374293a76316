"""The gated delta rule with a decay per channel (Kimi delta attention,
arXiv:2510.26692, on DeltaNet's chunked form), as Pallas TPU kernels: one
forward and one backward, each a sequential walk over the chunks that keeps
a chunk's decays, pair matrices and solve in VMEM.

Per head, with a key ``k_t`` and a query ``q_t`` (K values), a value ``v_t``
(V values), a log-decay a channel ``g_t <= 0`` (K values, ``alpha_t =
exp(g_t)``) and a step ``beta_t`` in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                (S: K x V, S_{-1} = 0)

The state is *corrected*: what it already answers for ``k_t`` is taken out
before ``v_t`` goes in.  Written as ``S_t = Diag(alpha_t) S_{t-1} + k_t
u_t^T`` the correction is in the pseudo-value ``u_t = beta_t (v_t - S_{t-1}^T
Diag(alpha_t) k_t)``, which depends on every earlier ``u`` of its chunk.

How it runs.  The sequence is cut into chunks of ``chunk`` positions.  With
``G_t`` the running sum of ``g`` inside a chunk (``Gamma = exp(G)``) and
``S`` the state the chunk starts from, position ``i`` reaches position ``t
>= i`` through the decay ``exp(G_t - G_i)`` a channel, and the chunk's
pseudo-values solve a unit lower-triangular system (the WY / UT form)::

    A[t, i] = beta_t sum_c k_tc k_ic exp(G_tc - G_ic)          i <  t
    B[t, i] =        sum_c q_tc k_ic exp(G_tc - G_ic)          i <= t
    U        = (I + A)^-1 Diag(beta) (V - (K o Gamma) S)
    O        = (Q o Gamma) S + B U
    S'       = Diag(Gamma_last) S + (K o exp(G_last - G))^T U

(``U`` is ``U0 - W S`` of ``[W | U0] = (I + A)^-1 Diag(beta) [K o Gamma |
V]``, with the solve applied once.)  The forward kernel's grid walks blocks
of heads and, in order, the chunks; each head's state lives in VMEM across
the chunks.  The backward kernel walks the chunks in reverse carrying the
state's cotangent, rebuilds a chunk's decays, ``A``, ``B`` and solve from q,
k, g and beta, and reads the chunk's starting state and ``U`` that the
forward wrote (``KDA_RESIDUAL_NAMES``); nothing of a chunk but those goes
through HBM.

**The decays of a pair are never split over a whole chunk.**  ``exp(G_t -
G_i)`` is at most 1, but as the product of ``exp(G_t)`` and ``exp(-G_i)``
the second factor is ``exp(320)`` after 64 steps at a gate's bound of -5,
which no float32 holds.  The rows go a sub-block of ``sub_block`` positions
at a time, and a sub-block's decays are taken from the running sum ``R`` at
its middle: its rows carry ``exp(G_t - R)`` and its own columns ``exp(R -
G_i)``, both within ``exp(+-sub_block max|g| / 2)``; the columns before it
carry ``exp(R - G_i) <= 1``, and the columns after it, which the mask
drops, no decay at all.  At 16 positions and a bound of -5 that is
``exp(+-40)``, which float32 and bfloat16 hold with room at both ends (taken
from the sum at the sub-block's *start* the rows would carry ``exp(-80)``,
and a component of ``k`` under 6e-4 times that is flushed to nought, with it
a pair whose decay is near 1: the gradients then read 1e-4 off).
**``sub_block`` times the largest ``|g|`` must stay under about 100.**  The
products stay matrix products over the channels; what is made beside ``k``
is one column panel a sub-block, in the products' precision.

**The solve** is ``(I + A)^-1`` made by blocks that double (2, 4, ..., the
chunk): a block's inverse from its two halves' is ``[T1 0; -T2 A21 T1
T2]``, which is substitution by blocks, not a series, so no power of ``A``
is formed (a Neumann series of a chunk's keys alike would cancel terms of
``2^62``).

The running sums, the decays, the solve and the carried state are float32
(float32 products at full precision); the operands of every other product
are in ``v``'s dtype with float32 accumulation.  A length that is no
multiple of ``chunk`` is padded at the end with steps of ``g = 0``, ``beta =
0`` and ``k = 0`` (the state stands still) and the padding is cut off the
result.

Off the TPU the kernels run in Pallas' interpreter; which body was built is
counted at trace time (``ops.kda_trace_total{mode=interpret|mosaic}``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from colearn_federated_learning_tpu import telemetry

# What the rule's forward leaves its backward, by the names a
# ``jax.checkpoint`` policy may keep (``save_only_these_names``): each
# chunk's starting state and its pseudo-values, and the rule's output, so a
# rematerialised layer that keeps all three runs no rule kernel again.
KDA_RESIDUAL_NAMES = ("kda_states", "kda_pseudo_values", "kda_out")

_F32 = jnp.float32


def _sub_block(chunk: int, sub_block: int) -> int:
    if chunk < 1 or sub_block < 1 or chunk % sub_block:
        raise ValueError(
            f"a chunk of {chunk} is not whole sub-blocks of {sub_block}")
    return chunk // sub_block


def _interpret() -> bool:
    """Off the TPU the kernels run interpreted."""
    return jax.default_backend() != "tpu"


def _mode() -> bool:
    """Whether to interpret, counted at trace time: which body was built is
    not visible in the result, and a chip run that traced the interpreter
    is a fault."""
    interpret = _interpret()
    telemetry.get_registry().counter(
        "ops.kda_trace_total",
        labels={"mode": "interpret" if interpret else "mosaic"}).inc()
    return interpret


def _heads_per_step(heads: int, *widths: int) -> int:
    """Heads a grid step takes: the fewest whose lanes fill 512 (whole
    128-lane tiles), or all of them."""
    for n in range(1, heads):
        if heads % n == 0 and all(
                n * w % 128 == 0 and n * w >= 512 for w in widths):
            return n
    return heads


def _mm(a, b, dtype, *, lhs_t=False, rhs_t=False):
    """``a @ b`` (``a^T``, ``b^T`` where asked), the operands in ``dtype``
    and float32 sums; float32 operands at full precision."""
    dims = (((0 if lhs_t else 1,), (1 if rhs_t else 0,)), ((), ()))
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims,
        precision=lax.Precision.HIGHEST if dtype == _F32 else None,
        preferred_element_type=_F32)


def _mm_fine(a, b, dtype, **dims):
    """``_mm`` with ``b``, an operand the forward rounded to ``dtype``, in
    two parts of ``dtype`` (its high bits and the rest): the pairs'
    gradients, whose halves from the rows and from the columns cancel in
    the running sum from the end, where a rounded operand would leave the
    gates' gradient twice as far from the recurrence."""
    if dtype == _F32:
        return _mm(a, b, dtype, **dims)
    high = b.astype(dtype)
    return (_mm(a, high, dtype, **dims)
            + _mm(a, b - high.astype(_F32), dtype, **dims))


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(row):
    """(1, n) as (n, 1)."""
    n = row.shape[-1]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _row(column):
    """(n, 1) as (1, n)."""
    n = column.shape[0]
    eye = _iota((n, n), 0) == _iota((n, n), 1)
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _ones_below(chunk: int, upper: bool = False):
    """``L[t, s] = 1`` where ``s <= t`` (the running sum), or where ``s >=
    t`` with ``upper`` (its transpose, the sum from the end)."""
    t, s = _iota((chunk, chunk), 0), _iota((chunk, chunk), 1)
    return jnp.where(s >= t if upper else s <= t, 1.0, 0.0)


def _heads_of(n: int, chunk: int, heads: int, axis: int = 0):
    """A row's (``axis`` 0, shape (n, 1)) or a column's (1, (1, n)) head in
    a stack of ``heads`` chunks, or in two such stacks one under the other,
    and its place in its chunk."""
    shape = (n, 1) if axis == 0 else (1, n)
    t = _iota(shape, axis)
    if n > heads * chunk:
        t = jnp.where(t >= heads * chunk, t - heads * chunk, t)
    head = jnp.zeros(shape, jnp.int32)
    for h in range(1, heads):
        head = head + jnp.where(t >= h * chunk, 1, 0)
    return head, t - chunk * head


def _per_head(rows, head):
    """Each row's head's row of ``rows`` (one (1, K) a head)."""
    out = rows[0]
    for h in range(1, len(rows)):
        out = jnp.where(head == h, rows[h], out)
    return out


def _spread(x, head, heads: int):
    """(rows, d) as (rows, heads d) with a row's values in its head's lanes
    and nought elsewhere: one product then serves every head of a stack,
    each against its own state."""
    if heads == 1:
        return x
    width = x.shape[1]
    lane, _ = _heads_of(heads * width, width, heads, axis=1)
    return jnp.where(head == lane, jnp.concatenate([x] * heads, axis=1), 0.0)


def _gather(y, head, heads: int):
    """(rows, heads d) back to (rows, d): each row's own head's lanes."""
    width = y.shape[1] // heads
    return _per_head([y[:, h * width:(h + 1) * width]
                      for h in range(heads)], head)


class _Chunk:
    """What a stack of ``heads`` heads' chunks (``n`` = heads x chunk rows)
    needs that no state enters: the rows' heads and places, the decays, the
    stacked rows of the pairs' products, ``A`` before its steps, ``B``, the
    solve, and the decays to and from the chunks' ends."""

    def __init__(self, q, k, sums, beta, dtype, sub: int, chunk: int,
                 heads: int):
        n = k.shape[0]
        self.n, self.chunk, self.heads = n, chunk, heads
        self.head, place = _heads_of(n, chunk, heads)
        # Decays of a sub-block's rows and columns, from the sum at its
        # middle.
        middles = [_per_head([sums[h * chunk + r * sub + sub // 2:
                                   h * chunk + r * sub + sub // 2 + 1]
                              for h in range(heads)], self.head)
                   for r in range(chunk // sub)]
        middle = middles[0]
        for r in range(1, len(middles)):
            middle = jnp.where(place >= r * sub, middles[r], middle)
        self.rows = jnp.exp(sums - middle)
        self.reach = [jnp.exp(jnp.where(place < (r + 1) * sub, m - sums, 0.0))
                      for r, m in enumerate(middles)]
        # The pairs, by sub-block of the row; a row meets its head's keys.
        self.x = jnp.concatenate([k * self.rows, q * self.rows], axis=0)
        _, place2 = _heads_of(2 * n, chunk, heads)
        self.in_sub = [(place2 >= r * sub) & (place2 < (r + 1) * sub)
                       for r in range(len(self.reach))]
        pairs = None
        for factor, picked in zip(self.reach, self.in_sub):
            p = _mm(self.x, k * factor, dtype, rhs_t=True)
            pairs = p if pairs is None else jnp.where(picked, p, pairs)
        col_head, _ = _heads_of(n, chunk, heads, axis=1)
        t, s = _iota((n, n), 0), _iota((n, n), 1)
        same = self.head == col_head
        self.below, self.upto = same & (s < t), same & (s <= t)
        self.a_bar = jnp.where(self.below, pairs[:n], 0.0)
        self.b = jnp.where(self.upto, pairs[n:], 0.0)
        span = chunk if chunk & (chunk - 1) == 0 else n
        self.solve = _unit_lower_inverse(beta * self.a_bar, span)
        self.gamma = jnp.exp(sums)
        self.lasts = [sums[(h + 1) * chunk - 1:(h + 1) * chunk]
                      for h in range(heads)]
        self.to_end = jnp.exp(_per_head(self.lasts, self.head) - sums)
        # Each head's decay over the whole chunk, (heads K, 1).
        self.whole = jnp.concatenate(
            [_column(jnp.exp(last)) for last in self.lasts], axis=0)

    def spread(self, x):
        head = self.head
        if x.shape[0] > self.n:
            head = jnp.concatenate([head, head], axis=0)
        return _spread(x, head, self.heads)


def _unit_lower_inverse(a, span: int):
    """``(I + a)^-1`` for ``a`` strictly lower triangular (n, n), float32:
    blocks of 2, then blocks of twice the size from two inverted halves,
    ``[T1 0; -T2 A21 T1 T2]``, up to ``span``."""
    n = a.shape[0]
    t, s = _iota((n, n), 0), _iota((n, n), 1)
    inverse = jnp.where(t == s, 1.0, 0.0) - jnp.where(
        (t >> 1) == (s >> 1), a, 0.0)
    shift = 1
    while (1 << shift) < span:
        shift += 1
        joined = ((t >> shift) == (s >> shift)) & (
            (t >> (shift - 1)) != (s >> (shift - 1)))
        inverse = inverse - _mm(
            _mm(inverse, jnp.where(joined, a, 0.0), _F32), inverse, _F32)
    return inverse


def _forward_heads(q, k, v, sums, beta, state, dtype, sub: int, chunk: int,
                   heads: int):
    """A stack of ``heads`` heads' chunks, one under the other: ``q``, ``k``
    (n, K), ``v`` (n, V), the running sums (n, K), ``beta`` (n, 1), the
    states they start from (heads K, V), all float32.  Returns ``O``,
    ``U`` (n, V) and the states after."""
    c = _Chunk(q, k, sums, beta, dtype, sub, chunk, heads)
    n = c.n
    read = _mm(c.spread(jnp.concatenate([k * c.gamma, q * c.gamma], axis=0)),
               state, dtype)
    u = _mm(c.solve, beta * (v - read[:n]), _F32)
    out = read[n:] + _mm(c.b, u, dtype)
    after = c.whole * state + _mm(c.spread(k * c.to_end), u, dtype,
                                  lhs_t=True)
    return out, u, after


def _backward_heads(q, k, v, sums, beta, state, u, d_out, d_after, dtype,
                    sub: int, chunk: int, heads: int):
    """The same stack backward, all float32: the forward's arguments, the
    states it started from and its ``U`` as the forward kept them, the
    output's cotangent and the cotangents of the states it left.  Returns
    the cotangents of q, k, v, the running sums, beta and the states it
    started from."""
    c = _Chunk(q, k, sums, beta, dtype, sub, chunk, heads)
    n, head = c.n, c.head
    kg, qg, ke = k * c.gamma, q * c.gamma, k * c.to_end

    d_u = (_mm(c.b, d_out, dtype, lhs_t=True)
           + _mm(c.spread(ke), d_after, dtype))
    d_x = _mm(c.solve, d_u, _F32, lhs_t=True)         # (I + A)^-T dU
    d_v = beta * d_x
    d_a = jnp.where(c.below, -_mm(d_x, u, _F32, rhs_t=True), 0.0)
    d_beta = (jnp.sum(d_x * (v - _mm(c.spread(kg), state, dtype)), axis=1,
                      keepdims=True)
              + jnp.sum(d_a * c.a_bar, axis=1, keepdims=True))
    d_b = jnp.where(c.upto, _mm(d_out, u, dtype, rhs_t=True), 0.0)
    d_before = (c.whole * d_after
                + _mm(c.spread(qg), d_out, dtype, lhs_t=True)
                - _mm(c.spread(kg), d_v, dtype, lhs_t=True))
    d_read = _mm(jnp.concatenate([d_out, -d_v], axis=0), state, dtype,
                 rhs_t=True)
    d_qg = _gather(d_read[:n], head, heads)
    d_kg = _gather(d_read[n:], head, heads)
    d_ke = _gather(_mm(u, d_after, dtype, rhs_t=True), head, heads)

    # The pairs: the rows' factor (left) and each sub-block's panel (right).
    d_pairs = jnp.concatenate([beta * d_a, d_b], axis=0)
    d_left, d_right = None, 0.0
    for factor, in_sub in zip(c.reach, c.in_sub):
        picked = jnp.where(in_sub, d_pairs, 0.0)
        left = _mm_fine(picked, k * factor, dtype)
        d_left = left if d_left is None else d_left + left
        d_right = d_right + factor * _mm_fine(picked, c.x, dtype, lhs_t=True)
    d_k_left, d_q_left = c.rows * d_left[:n], c.rows * d_left[n:]

    d_q = c.gamma * d_qg + d_q_left
    d_k = c.gamma * d_kg + c.to_end * d_ke + d_k_left + d_right
    d_sums = (qg * d_qg + kg * d_kg - ke * d_ke
              + q * d_q_left + k * d_k_left - k * d_right)
    # What each head's last sum reaches: the decays to the chunk's end and
    # over the whole chunk.
    d_whole = jnp.sum(state * d_after, axis=1, keepdims=True)
    width = k.shape[1]
    t = _iota((n, 1), 0)
    for h, last in enumerate(c.lasts):
        extra = (jnp.sum(jnp.where(head == h, ke * d_ke, 0.0), axis=0,
                         keepdims=True)
                 + jnp.exp(last) * _row(d_whole[h * width:(h + 1) * width]))
        d_sums = d_sums + jnp.where(t == (h + 1) * chunk - 1, extra, 0.0)
    return d_q, d_k, d_v, d_sums, d_beta, d_before


# The rows a stack of heads' chunks may fill: the matrix unit's side.
_STACK_ROWS = 128


def _stacks(heads: int, chunk: int, key_dim: int, v_dim: int):
    """A grid step's heads in stacks: the most heads (dividing the step's)
    whose chunks fill no more than ``_STACK_ROWS`` rows, each stack's heads
    with their lanes."""
    size = max(n for n in range(1, heads + 1)
               if heads % n == 0 and (n == 1 or n * chunk <= _STACK_ROWS))
    return [[(h, slice(h * key_dim, (h + 1) * key_dim),
              slice(h * v_dim, (h + 1) * v_dim))
             for h in range(first, first + size)]
            for first in range(0, heads, size)]


def _step_of(betas, j):
    """Head ``j``'s steps, (C, 1), from a block's (C, heads)."""
    lane = _iota(betas.shape, 1)
    return jnp.sum(jnp.where(lane == j, betas, 0.0), axis=1, keepdims=True)


def _stacked(ref, slices):
    """The chunks of a (1, C, heads d) block at ``slices``, one under the
    other, float32."""
    return jnp.concatenate([ref[0, :, lanes].astype(_F32)
                            for lanes in slices], axis=0)


def _stack_inputs(stack, q_ref, k_ref, v_ref, sums, betas):
    """A stack's q, k, v, running sums and steps, one head under the
    other."""
    heads, keys, values = zip(*stack)
    return (_stacked(q_ref, keys), _stacked(k_ref, keys),
            _stacked(v_ref, values),
            jnp.concatenate([sums[:, lanes] for lanes in keys], axis=0),
            jnp.concatenate([_step_of(betas, h) for h in heads], axis=0))


def _forward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, *refs,
                    heads, key_dim, v_dim, sub, dtype):
    """A block of ``heads`` heads, one chunk; ``refs``: the chunk's starting
    states and ``U`` where kept, then the carried states (VMEM)."""
    *kept, state_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    chunk = q_ref.shape[1]
    sums = _mm(_ones_below(chunk), g_ref[0], _F32)
    for stack in _stacks(heads, chunk, key_dim, v_dim):
        state = jnp.concatenate([state_ref[h] for h, _, _ in stack], axis=0)
        out, u, after = _forward_heads(
            *_stack_inputs(stack, q_ref, k_ref, v_ref, sums, beta_ref[0, 0]),
            state, dtype, sub, chunk, len(stack))
        for j, (h, _, vv) in enumerate(stack):
            rows, keys = (slice(j * chunk, (j + 1) * chunk),
                          slice(j * key_dim, (j + 1) * key_dim))
            out_ref[0, :, vv] = out[rows].astype(out_ref.dtype)
            if kept:
                states_ref, u_ref = kept
                states_ref[0, 0, h] = state[keys].astype(states_ref.dtype)
                u_ref[0, :, vv] = u[rows].astype(u_ref.dtype)
            state_ref[h] = after[keys]


def _backward_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, u_ref,
                     do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                     carry_ref, *, heads, key_dim, v_dim, sub, dtype):
    """A block of ``heads`` heads, one chunk, the chunks in reverse;
    ``carry_ref`` (VMEM) holds the cotangent of the states the chunk
    leaves."""

    @pl.when(pl.program_id(2) == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    chunk = q_ref.shape[1]
    sums = _mm(_ones_below(chunk), g_ref[0], _F32)
    betas = beta_ref[0, 0]
    d_betas = jnp.zeros(betas.shape, _F32)
    lane = _iota(betas.shape, 1)
    d_sums = [None] * heads
    for stack in _stacks(heads, chunk, key_dim, v_dim):
        hs, _, values = zip(*stack)
        d_q, d_k, d_v, d_s, d_beta, d_before = _backward_heads(
            *_stack_inputs(stack, q_ref, k_ref, v_ref, sums, betas),
            jnp.concatenate([states_ref[0, 0, h].astype(_F32) for h in hs],
                            axis=0),
            _stacked(u_ref, values), _stacked(do_ref, values),
            jnp.concatenate([carry_ref[h] for h in hs], axis=0),
            dtype, sub, chunk, len(stack))
        for j, (h, kk, vv) in enumerate(stack):
            rows = slice(j * chunk, (j + 1) * chunk)
            dq_ref[0, :, kk] = d_q[rows].astype(dq_ref.dtype)
            dk_ref[0, :, kk] = d_k[rows].astype(dk_ref.dtype)
            dv_ref[0, :, vv] = d_v[rows].astype(dv_ref.dtype)
            d_sums[h] = d_s[rows]
            d_betas = jnp.where(lane == h, d_beta[rows], d_betas)
            carry_ref[h] = d_before[j * key_dim:(j + 1) * key_dim]
    dg_ref[0] = _mm(_ones_below(chunk, upper=True),
                    jnp.concatenate(d_sums, axis=1), _F32)
    dbeta_ref[0, 0] = d_betas


def _grid(q, v, beta, chunk: int, reverse: bool):
    """The grid (batch, blocks of heads, chunks), the specs of a chunk's
    blocks by kind, and the sizes."""
    batch, length, _ = q.shape
    _, blocks, _, heads = beta.shape
    key_dim = q.shape[-1] // (blocks * heads)
    v_dim = v.shape[-1] // (blocks * heads)
    n = length // chunk

    def at(c):
        return n - 1 - c if reverse else c

    def lanes(width):
        return pl.BlockSpec((1, chunk, heads * width),
                            lambda b, h, c: (b, at(c), h))

    specs = {
        "key": lanes(key_dim),
        "value": lanes(v_dim),
        "step": pl.BlockSpec((1, 1, chunk, heads),
                             lambda b, h, c: (b, h, at(c), 0)),
        "state": pl.BlockSpec((1, 1, heads, key_dim, v_dim),
                              lambda b, h, c: (b, at(c), h, 0, 0)),
    }
    sizes = dict(heads=heads, key_dim=key_dim, v_dim=v_dim)
    return (batch, blocks, n), specs, sizes


# Batch and blocks of heads in any order; the chunks in order.
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, g, beta, chunk: int, sub: int, keep: bool):
    """``q``, ``k``, ``g`` (B, L, H K); ``v`` (B, L, H V); ``beta`` (B, H /
    heads, L, heads).  Returns the output (B, L, H V) in ``v``'s dtype and,
    with ``keep``, each chunk's starting state (B, L / chunk, H, K, V) and
    ``U`` (B, L, H V), in ``v``'s dtype."""
    interpret = _mode()
    grid, specs, sizes = _grid(q, v, beta, chunk, reverse=False)
    batch, blocks, n = grid
    heads, key_dim, v_dim = sizes["heads"], sizes["key_dim"], sizes["v_dim"]
    wide = jax.ShapeDtypeStruct(v.shape, v.dtype)
    out_specs, out_shape = [specs["value"]], [wide]
    if keep:
        out_specs += [specs["state"], specs["value"]]
        out_shape += [jax.ShapeDtypeStruct(
            (batch, n, blocks * heads, key_dim, v_dim), v.dtype), wide]
    kernel = functools.partial(_forward_kernel, sub=sub, dtype=v.dtype,
                               **sizes)
    result = pl.pallas_call(
        kernel,
        name="kda_fwd",
        grid=grid,
        in_specs=[specs["key"], specs["key"], specs["value"], specs["key"],
                  specs["step"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, key_dim, v_dim), _F32)],
        interpret=interpret,
        compiler_params=_PARAMS,
    )(q, k, v, g, beta)
    return tuple(result) if keep else result[0]


def _backward(q, k, v, g, beta, states, u, d_out, chunk: int, sub: int):
    interpret = _mode()
    grid, specs, sizes = _grid(q, v, beta, chunk, reverse=True)
    kernel = functools.partial(_backward_kernel, sub=sub, dtype=v.dtype,
                               **sizes)
    key, value, step = specs["key"], specs["value"], specs["step"]
    return pl.pallas_call(
        kernel,
        name="kda_bwd",
        grid=grid,
        in_specs=[key, key, value, key, step, specs["state"], value, value],
        out_specs=[key, key, value, key, step],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                   for a in (q, k, v, g, beta)],
        scratch_shapes=[pltpu.VMEM(
            (sizes["heads"], sizes["key_dim"], sizes["v_dim"]), _F32)],
        interpret=interpret,
        compiler_params=_PARAMS,
    )(q, k, v, g, beta, states, u, d_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk, sub):
    """The primal call (the evaluation's) writes the output alone."""
    return _forward(q, k, v, g, beta, chunk, sub, keep=False)


def _rule_fwd(q, k, v, g, beta, chunk, sub):
    out, states, u = _forward(q, k, v, g, beta, chunk, sub, keep=True)
    states, u, out = (checkpoint_name(a, name) for a, name in zip(
        (states, u, out), KDA_RESIDUAL_NAMES))
    return out, (q, k, v, g, beta, states, u)


def _rule_bwd(chunk, sub, kept, d_out):
    # The backward pass is traced apart from the forward's scopes.
    with telemetry.device_scope("kda.rule"):
        return tuple(_backward(*kept, d_out, chunk, sub))


_rule.defvjp(_rule_fwd, _rule_bwd)


def kda_chunked(q, k, v, g, beta, *, chunk: int = 64, sub_block: int = 16):
    """``q``, ``k``, ``g``: (B, L, H, K); ``v``: (B, L, H, V); ``beta``: (B,
    L, H).  ``g`` is the log of the decay, at most 0.  Returns ``o``: (B, L,
    H, V) in ``v``'s dtype."""
    batch, length, heads, key_dim = k.shape
    v_dim, dtype = v.shape[-1], v.dtype
    sub_block = min(sub_block, chunk)
    _sub_block(chunk, sub_block)
    per_step = _heads_per_step(heads, key_dim, v_dim)
    pad = -length % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    padded = length + pad

    def lanes(a, to):
        """(B, L, H, d) as (B, L, H d): a reshape, no copy."""
        return a.astype(to).reshape(batch, padded, -1)

    steps = beta.astype(_F32).reshape(
        batch, padded, heads // per_step, per_step).swapaxes(1, 2)
    out = _rule(lanes(q, dtype), lanes(k, dtype), lanes(v, dtype),
                lanes(g, _F32), steps, chunk, sub_block)
    return out.reshape(batch, padded, heads, v_dim)[:, :length]


def kda_recurrent(q, k, v, g, beta):
    """The same rule one position at a time, float32: what the kernels are
    held to (``tests/test_kda.py``)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))

    def step(state, this):
        q_t, k_t, v_t, g_t, beta_t = this                   # (B, H, ..)
        state = state * jnp.exp(g_t)[..., None]
        u = beta_t[..., None] * (
            v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state,
                             precision=lax.Precision.HIGHEST))
        state = state + k_t[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state,
                                 precision=lax.Precision.HIGHEST)

    batch, _, heads, key_dim = k.shape
    _, out = lax.scan(
        step, jnp.zeros((batch, heads, key_dim, v.shape[-1]), f32),
        tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    return out.swapaxes(0, 1)
