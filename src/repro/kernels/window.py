"""The staged input window both sparse conv kernels read.

The ELL kernel (``kernels/sparse_conv``) and the BCSR kernel
(``kernels/bsr_conv``) stage one halo'd band of the padded input per row
tile into VMEM and read every tap's (TE, F) window out of it.  This module
holds that layout in one place: its shape (:func:`stage_shape`, which the
VMEM budget in ``kernels/budget.py`` prices too), the wrapper-side column
phase split (:func:`phase_split`), and the in-kernel window load
(:func:`load_window`).

Mosaic layout (what the TPU compiler accepts): a vector load may start at
a dynamic sublane offset, with a static sublane stride, but its lane offset
must be static and its lane stride 1.  So the filter row ``r`` and the
stride stay on the sublane axis (``pl.ds(r, TE, stride)``) and the filter
column ``s`` comes off the lane axis in two moves: the wrapper splits the
padded input's columns into ``min(stride, S)`` phase planes (column
``j*stride + p`` lands in plane ``p`` at column ``j``), so tap ``s`` reads
plane ``s % stride`` at the static lane offset ``s // stride``; and the
kernel branches once per tap on the decoded ``s`` (``lax.switch`` over the
S static offsets).  Stride 1 is one plane and no copy.  Columns are never
tiled: a kernel stages whole lane rows, so its output tile covers all F
columns.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def sublane_tile(itemsize: int) -> int:
    """Rows in one (sublane, 128) VMEM tile: 8 rows of 32-bit words, 16 of
    a 2-byte type, 32 of a 1-byte type."""
    return 8 * max(1, 4 // itemsize)


def halo_extent(t: int, stride: int, r: int) -> int:
    """Input rows/cols one output tile of ``t`` positions touches."""
    return (t - 1) * stride + r


def stage_shape(c: int, r: int, s: int, stride: int, te: int, f: int,
                itemsize: int = 4) -> tuple:
    """(planes, rows, cols) of the halo block one row tile stages:
    ``C * min(stride, S)`` column-phase planes of ``(TE-1)*stride + R`` rows
    by ``F + (S-1)//stride`` phase-plane columns, both rounded up to whole
    (sublane, 128) tiles.  The staging DMA must move whole tiles: on a v5e
    a copy of a partial sublane tile into a VMEM buffer never signals its
    semaphore, and the kernel hangs in its wait."""
    rows = round_up(halo_extent(te, stride, r), sublane_tile(itemsize))
    cols = round_up(f + (s - 1) // stride, 128)
    return c * min(stride, s), rows, cols


def phase_split(xpad: jax.Array, *, s: int, stride: int, rows: int,
                cols: int) -> jax.Array:
    """Zero-pad (or crop) ``xpad`` to ``rows`` x ``cols * stride`` and split
    its columns into ``min(stride, S)`` phase planes.

    Plane ``p`` of channel ``c`` sits at index ``c * P + p`` and holds input
    columns ``p, p + stride, p + 2*stride, ...``, so every tap's window is
    a stride-1 lane slice at the static offset ``s // stride`` of plane
    ``s % stride``.  ``cols`` counts phase-plane columns.  Stride 1 is the
    identity layout (one plane), so only strided layers pay the copy.
    """
    n, c, hp, wp = xpad.shape
    width = cols * stride
    xpad = xpad[:, :, :rows, :width]
    if rows > hp or width > wp:
        xpad = jnp.pad(xpad, ((0, 0), (0, 0), (0, max(0, rows - hp)),
                              (0, max(0, width - wp))))
    if stride == 1:
        return xpad
    planes = min(stride, s)
    x = xpad.reshape(n, c, rows, cols, stride)[..., :planes]
    return x.transpose(0, 1, 4, 2, 3).reshape(n, c * planes, rows, cols)


def load_window(xblk, lead: tuple, c, r, s_dyn, *, s: int, stride: int,
                te: int, f: int) -> jax.Array:
    """The (TE, F) input window of tap (c, r, s) from a staged halo block.

    ``lead`` indexes the scratch buffer (the pipelined slot, or nothing).
    The row start is dynamic with a static sublane stride; the column offset
    must be static, so the decoded ``s`` selects one of S static loads."""
    planes = min(stride, s)
    rows = pl.ds(r, te) if stride == 1 else pl.ds(r, te, stride=stride)

    def tap(sv):
        return lambda: xblk[(*lead, c * planes + sv % stride, rows,
                             pl.ds(sv // stride, f))]

    if s == 1:
        return tap(0)()
    return lax.switch(s_dyn, [tap(sv) for sv in range(s)])
