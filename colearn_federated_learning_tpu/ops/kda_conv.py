"""Kimi delta attention's short convolution and what follows it
(``models/ling3.py`` ``KdaMixer``), as one Pallas TPU kernel each way.

From the float32 sums of the stream's maps ``x`` (B, L, 3 H d + more: q, k,
v, then the columns the mixer's gates read), per channel::

    y_t = sum_j taps[j] x_{t - (K - 1) + j}        (K taps, x_{<0} = 0)
    s   = silu(y)
    q   = d^-1/2 s / |s|_2 ,  k = s / |s|_2 ,  v = s   (norms over a head's d)

with q, k and v rounded to ``dtype`` once, at the end.  The forward kernel
reads each block of columns of ``x`` where it lies (no sliced copy) and
writes the block's head or heads of q, k or v; the columns after v are
handed back as they are.  The backward kernel makes the convolution, the
SiLU and the norms again from ``x``, takes their derivatives and applies the
taps looking ahead, ``dx_t = sum_j taps[j] dy_{t + (K - 1) - j}``, writing
``dx`` in float32 and each block's sums for the taps' cotangent; what a
layer keeps for it is ``x`` and the taps alone.

How it runs.  The grid walks the batch and blocks of whole heads' columns (a
block is all q, all k or all v, told by its index), each block the whole
sequence; inside, a loop walks the positions a slab of rows at a time and
carries the 8 rows the next slab's taps reach: forward, the last rows of
``x``; backward, walking from the end, the first rows of ``dy``.  The three
outputs' blocks are chosen from the column index with the others' held
still, so each block of q, k and v is written once.

Off the TPU the kernels run in Pallas' interpreter; which body was built is
counted at trace time (``ops.kda_conv_trace_total{mode=interpret|mosaic}``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from colearn_federated_learning_tpu import telemetry

_F32 = jnp.float32
# Rows carried across a slab's edge: at least the taps' reach, K - 1.
_CARRY = 8
# Rows a step of the loop over positions takes, at most.
_SLAB = 256


def _interpret() -> bool:
    """Off the TPU the kernels run interpreted."""
    return jax.default_backend() != "tpu"


def _mode() -> bool:
    """Whether to interpret, counted at trace time."""
    interpret = _interpret()
    telemetry.get_registry().counter(
        "ops.kda_conv_trace_total",
        labels={"mode": "interpret" if interpret else "mosaic"}).inc()
    return interpret


def _lanes(heads: int, head_dim: int) -> int:
    """Columns a block takes: the fewest whole heads that fill whole
    128-lane tiles, or all of them."""
    for n in range(1, heads):
        if heads % n == 0 and n * head_dim % 128 == 0:
            return n * head_dim
    return heads * head_dim


def _slab(length: int) -> int:
    """The largest multiple of 8 up to ``_SLAB`` that divides the length, or
    the whole length."""
    for rows in range(min(_SLAB, length) // 8 * 8, 0, -8):
        if length % rows == 0:
            return rows
    return length


def _head_sums(a, head_dim: int):
    """Each row's sum over each head's ``head_dim`` lanes, broadcast over
    the head."""
    width = a.shape[-1]
    if width == head_dim:
        return jnp.sum(a, axis=-1, keepdims=True)
    return jnp.concatenate(
        [jnp.broadcast_to(jnp.sum(a[:, h:h + head_dim], axis=-1,
                                  keepdims=True), (a.shape[0], head_dim))
         for h in range(0, width, head_dim)], axis=1)


def _rows_from(window, offset: int, rows: int):
    """``window[offset : offset + rows]``; where the offset is no whole
    number of sublane tiles, as a rotation of the window's rows and an
    aligned slice (the rotation is the cross-lane unit's, a shift of an
    unaligned slice the vector unit's)."""
    if offset % 8 == 0:
        return window[offset:offset + rows]
    total = window.shape[0]
    return pltpu.roll(window, total - offset, 0)[:rows]


def _made(before, x, taps, head_dim: int, eps: float, normed: bool):
    """A slab's ``x`` shifted by each tap, ``y``, ``sigmoid(y)``, ``s =
    silu(y)`` and, if ``normed``, each head's inverse norm of ``s``, from
    the slab's ``x`` and the ``_CARRY`` rows before it."""
    reach = taps.shape[0] - 1
    window = jnp.concatenate([before, x], axis=0)
    shifted = [_rows_from(window, _CARRY - reach + j, x.shape[0])
               for j in range(reach + 1)]
    y = None
    for j, xj in enumerate(shifted):
        term = xj * taps[j:j + 1]
        y = term if y is None else y + term
    sig = jax.nn.sigmoid(y)
    s = y * sig
    inverse = (lax.rsqrt(_head_sums(s * s, head_dim) + eps) if normed
               else None)
    return shifted, y, sig, s, inverse


def _loop(n: int, step, init, reverse: bool = False):
    """``step(i, carry)`` over the ``n`` slabs, from the end if
    ``reverse``."""
    if n == 1:
        return step(0, init)
    return lax.fori_loop(
        0, n, lambda i, c: step(n - 1 - i if reverse else i, c), init)


def _rows(i, slab: int, n: int):
    return pl.ds(pl.multiple_of(i * slab, slab), slab) if n > 1 else slice(
        None)


def _by_kind(kind, run, refs, factors):
    """``run(ref, factor)`` for the block's kind alone: q's (``factor``
    the scale), k's (1) or v's (None: no norm)."""
    for j, (ref, factor) in enumerate(zip(refs, factors)):
        pl.when(kind == j)(functools.partial(run, ref, factor))


def _forward_kernel(x_ref, taps_ref, q_ref, k_ref, v_ref, *, blocks,
                    head_dim, scale, eps, slab):
    taps = taps_ref[...]
    n = x_ref.shape[1] // slab

    def run(out_ref, factor):
        def step(i, before):
            rows = _rows(i, slab, n)
            x = x_ref[0, rows, :]
            _, _, _, s, inverse = _made(before, x, taps, head_dim, eps,
                                        factor is not None)
            if factor is not None:
                s = s * inverse
                if factor != 1.0:
                    s = s * factor
            out_ref[0, rows, :] = s.astype(out_ref.dtype)
            return x[-_CARRY:]

        _loop(n, step, jnp.zeros((_CARRY, taps.shape[1]), _F32))

    _by_kind(pl.program_id(1) // blocks, run, (q_ref, k_ref, v_ref),
             (scale, 1.0, None))


def _backward_kernel(x_ref, taps_ref, dq_ref, dk_ref, dv_ref, dx_ref,
                     dtaps_ref, *, blocks, head_dim, scale, eps, slab):
    taps = taps_ref[...]
    reach, width = taps.shape[0] - 1, taps.shape[1]
    n = x_ref.shape[1] // slab

    def run(g_ref, factor):
        def step(i, carry):
            after, d_taps = carry
            rows = _rows(i, slab, n)
            x = x_ref[0, rows, :]
            if n > 1:
                first = pl.multiple_of(jnp.maximum(i * slab - _CARRY, 0),
                                       _CARRY)
                before = jnp.where(i > 0, x_ref[0, pl.ds(first, _CARRY), :],
                                   0.0)
            else:
                before = jnp.zeros((_CARRY, width), _F32)
            shifted, y, sig, s, inverse = _made(before, x, taps, head_dim,
                                                eps, factor is not None)
            ds = g_ref[0, rows, :].astype(_F32)
            if factor is not None:
                dn = ds * factor if factor != 1.0 else ds
                ds = inverse * dn - (inverse * inverse * inverse
                                     * _head_sums(dn * s, head_dim)) * s
            dy = ds * (sig * (1.0 + y * (1.0 - sig)))
            ahead = jnp.concatenate([dy, after], axis=0)
            dx = None
            for j in range(reach + 1):
                term = _rows_from(ahead, reach - j, x.shape[0]) * taps[j:j + 1]
                dx = term if dx is None else dx + term
            dx_ref[0, rows, :] = dx
            d_taps = d_taps + jnp.concatenate(
                [jnp.sum(xj * dy, axis=0, keepdims=True) for xj in shifted],
                axis=0)
            return ahead[:_CARRY], d_taps

        _, d_taps = _loop(n, step, (jnp.zeros((_CARRY, width), _F32),
                                    jnp.zeros(taps.shape, _F32)),
                          reverse=True)
        dtaps_ref[0] = d_taps

    _by_kind(pl.program_id(1) // blocks, run, (dq_ref, dk_ref, dv_ref),
             (scale, 1.0, None))


class _Grid:
    """The grid (batch, blocks of q's, k's and v's columns), each block the
    whole sequence, and the blocks' specs."""

    def __init__(self, x, taps, heads: int, head_dim: int):
        batch, self.length, _ = x.shape
        self.width = _lanes(heads, head_dim)
        self.blocks = heads * head_dim // self.width
        self.grid = (batch, 3 * self.blocks)
        self.slab = _slab(self.length)
        self.column = self.whole(lambda b, c: (b, 0, c))
        self.taps = pl.BlockSpec((taps.shape[0], self.width),
                                 lambda b, c: (0, c))

    def whole(self, at):
        return pl.BlockSpec((1, self.length, self.width), at)

    def kind(self, j: int):
        """The blocks of q (``j`` 0), k or v: the one a step of that kind
        writes or reads, held still at the other kinds' steps."""
        return self.whole(lambda b, c: (
            b, 0, jnp.clip(c - j * self.blocks, 0, self.blocks - 1)))

    def params(self, *itemsizes: int):
        """Batch in any order, the columns in order (a kind's blocks are
        held across the others'); VMEM for two buffers of each block of
        ``itemsizes`` bytes an element and the slabs' arrays."""
        block = self.length * self.width
        vmem = (2 * block * sum(itemsizes) + 32 * self.slab * self.width * 4
                + (4 << 20))
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(vmem, 16 << 20))


def _forward(x, taps, sizes, dtype):
    heads, head_dim, scale, eps = sizes
    grid = _Grid(x, taps, heads, head_dim)
    out = jax.ShapeDtypeStruct((x.shape[0], grid.length, heads * head_dim),
                               dtype)
    return tuple(pl.pallas_call(
        functools.partial(_forward_kernel, blocks=grid.blocks,
                          head_dim=head_dim, scale=scale, eps=eps,
                          slab=grid.slab),
        name="kda_conv_fwd",
        grid=grid.grid,
        in_specs=[grid.column, grid.taps],
        out_specs=[grid.kind(j) for j in range(3)],
        out_shape=[out] * 3,
        interpret=_mode(),
        compiler_params=grid.params(4, *[jnp.dtype(dtype).itemsize] * 3),
    )(x, taps))


def _backward(x, taps, d_qkv, sizes):
    heads, head_dim, scale, eps = sizes
    grid = _Grid(x, taps, heads, head_dim)
    batch = x.shape[0]
    return pl.pallas_call(
        functools.partial(_backward_kernel, blocks=grid.blocks,
                          head_dim=head_dim, scale=scale, eps=eps,
                          slab=grid.slab),
        name="kda_conv_bwd",
        grid=grid.grid,
        in_specs=[grid.column, grid.taps, *(grid.kind(j) for j in range(3))],
        out_specs=[grid.column, pl.BlockSpec(
            (1, *grid.taps.block_shape), lambda b, c: (b, 0, c))],
        out_shape=[
            jax.ShapeDtypeStruct((batch, grid.length, 3 * heads * head_dim),
                                 _F32),
            jax.ShapeDtypeStruct((batch, *taps.shape), _F32)],
        interpret=_mode(),
        compiler_params=grid.params(
            4, 4, *(jnp.dtype(d.dtype).itemsize for d in d_qkv)),
    )(x, taps, *d_qkv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _conv(x, taps, sizes, dtype):
    return (*_forward(x, taps, sizes, dtype), x[..., 3 * sizes[0] * sizes[1]:])


def _conv_fwd(x, taps, sizes, dtype):
    return _conv(x, taps, sizes, dtype), (x, taps)


def _conv_bwd(sizes, dtype, kept, cotangents):
    x, taps = kept
    *d_qkv, d_rest = cotangents
    # The backward pass is traced apart from the forward's scopes.
    with telemetry.device_scope("kda.conv"):
        dx, d_taps = _backward(x, taps, d_qkv, sizes)
        return (jnp.concatenate([dx, d_rest], axis=-1),
                jnp.sum(d_taps, axis=0))


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_qkv(x, taps, *, heads: int, head_dim: int, eps: float, dtype):
    """``x``: (B, L, 3 H d + more) float32, q's, k's and v's sums first;
    ``taps``: (K, 3 H d), K at most 9.  Returns q, k, v (B, L, H d) in
    ``dtype`` and the columns after v, ``x[..., 3 H d:]``."""
    if not 1 <= taps.shape[0] <= _CARRY + 1:
        raise ValueError(f"{taps.shape[0]} taps: at most {_CARRY + 1}")
    return _conv(x.astype(_F32), taps.astype(_F32),
                 (heads, head_dim, head_dim ** -0.5, eps), jnp.dtype(dtype))
