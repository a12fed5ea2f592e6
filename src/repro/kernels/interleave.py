"""Interleave two f32 planes into complex64's byte layout, on the chip.

The kernels work on split re/im f32 planes; a host that wants complex64
wants re and im alternating along the last axis. Joining the planes into a
complex64 array costs a device pass and, on the way to the host, the
runtime's one-thread join of the two halves of each element. This kernel
writes one f32 array ``(..., 2n)`` instead, ``[re0, im0, re1, im1, ...]``,
whose host copy is a plain f32 copy that views as complex64.

It only moves data, so the result holds the planes' values exactly. Each
128 x 128 tile of the planes is transposed so that range runs along
sublanes, the two transposed tiles are stored into alternate rows of a
(256, 128) scratch (sublane-strided stores), and the scratch is transposed
back into the tile's 256 output lanes: one HBM read of the planes and one
write of the result. Ragged dims are zero-padded up to the tile and sliced
after, as in ``transpose.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fft4step import auto_interpret

TILE = 128            # rows and columns interleaved per inner step
ROW_BLOCK = 256       # rows per grid step (when they divide the rows)
COL_BLOCK = 512       # columns per grid step (when they divide the columns)


def _interleave_kernel(a_ref, b_ref, o_ref, s_ref):
    rows, cols = a_ref.shape
    for i in range(rows // TILE):
        r = slice(i * TILE, (i + 1) * TILE)
        for j in range(cols // TILE):
            c = slice(j * TILE, (j + 1) * TILE)
            s_ref[pl.ds(0, TILE, stride=2), :] = a_ref[r, c].T
            s_ref[pl.ds(1, TILE, stride=2), :] = b_ref[r, c].T
            o_ref[r, 2 * j * TILE:2 * (j + 1) * TILE] = s_ref[...].T


def _block(n: int, most: int) -> int:
    return most if n % most == 0 else TILE


@functools.partial(jax.jit, static_argnames=("interpret",))
def interleave(xr, xi, *, interpret: Optional[bool] = None):
    """f32 planes ``(..., n)`` -> f32 ``(..., 2n)``, element ``2k`` of a
    row ``xr[..., k]`` and element ``2k + 1`` ``xi[..., k]``."""
    interpret = auto_interpret(interpret)
    *lead, n = xr.shape
    a = xr.reshape(-1, n)
    b = xi.reshape(-1, n)
    rows = a.shape[0]
    pr, pc = (-rows) % TILE, (-n) % TILE
    if pr or pc:
        a = jnp.pad(a, ((0, pr), (0, pc)))
        b = jnp.pad(b, ((0, pr), (0, pc)))
    rp, cp = rows + pr, n + pc
    tr, tc = _block(rp, ROW_BLOCK), _block(cp, COL_BLOCK)
    y = pl.pallas_call(
        _interleave_kernel,
        grid=(rp // tr, cp // tc),
        in_specs=[pl.BlockSpec((tr, tc), lambda i, j: (i, j))] * 2,
        out_specs=pl.BlockSpec((tr, 2 * tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rp, 2 * cp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((2 * TILE, TILE), jnp.float32)],
        interpret=interpret,
        name="interleave",
    )(a.astype(jnp.float32), b.astype(jnp.float32))
    if pr or pc:
        y = y[:rows, :2 * n]
    return y.reshape(*lead, 2 * n)
