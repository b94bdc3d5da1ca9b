"""Compressed sparse formats with the paper's *weight stretching* preprocessing.

Three formats:

``EllConv``   -- the paper's stretched-CSR conv weights, padded per-row to a
                 rectangular (ELL) layout so shapes are static under jit.  Each
                 output channel m keeps K = max-row-nnz entries of
                 (value, c, r, s, stretched offset).  Padding entries carry
                 value 0 and index 0, so they are mathematically inert.

``EllMatrix`` -- the same idea for 2-D weights (sparse linear layers); each row
                 keeps K column indices + values.

``BcsrMatrix``-- block compressed sparse row for the MXU path: per block-row,
                 a padded list of nonzero block-column ids plus the dense tile
                 data.  Zero-padded tiles point at block-column 0 with all-zero
                 data (inert).

Conversion happens once at model-load time on the host (numpy), exactly like
the paper's one-shot CSR construction + weight stretching; the jit-side
consumers only ever see fixed-shape arrays.

The conv formats (``EllConv``/``BcsrConv``) additionally support *quantised
value streams* (:func:`quantize_values` / :func:`dequantize`): the nonzero
values stored int8 or fp8 (``float8_e4m3fn``) with one f32 symmetric scale
per output channel, so the dominant HBM traffic of the sparse kernels
shrinks 4x while accumulation stays f32.  See the helper docstrings for the
round-trip error bounds.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# ELL conv format (paper's stretched CSR, rectangularised)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EllConv:
    """Sparse conv weights for a (M, C, R, S) filter bank.

    value:  (M, K) float   -- nonzero weights, zero-padded per row
    cidx:   (M, K) int32   -- input-channel index of each nonzero
    ridx:   (M, K) int32   -- filter-row index
    sidx:   (M, K) int32   -- filter-col index
    offset: (M, K) int32   -- *stretched* flat offset  c*Hp*Wp + r*Wp + s for a
                              padded input of shape (C, Hp, Wp); recomputed per
                              layer geometry by ``stretch_offsets``.
    nnz:    (M,)   int32   -- true row lengths (kernel loop bounds + balance)
    shape:  original (M, C, R, S)
    perm:   optional (M,) int32 -- row permutation of an *nnz-balanced* bank
                              (``balance_ell_conv``): row i of this bank is
                              output channel ``perm[i]`` of the original
                              filter bank.  None for banks in natural channel
                              order.  Consumers (``kernels.sparse_conv.ops``)
                              apply the inverse permutation to the output and
                              the forward permutation to bias/residual, so the
                              reordering is invisible outside the kernel.
    scale:  optional (M,) f32 -- per-output-channel symmetric dequantisation
                              scale of a *quantised* bank
                              (:func:`quantize_values`): the semantic weight
                              is ``value[m, j] * scale[m]`` in f32.  None for
                              banks whose values are stored at full width.
    """

    value: jax.Array
    cidx: jax.Array
    ridx: jax.Array
    sidx: jax.Array
    offset: jax.Array
    nnz: jax.Array
    shape: Tuple[int, int, int, int]
    perm: Optional[jax.Array] = None
    scale: Optional[jax.Array] = None

    @property
    def k(self) -> int:
        return int(self.value.shape[1])

    @property
    def value_dtype(self) -> str:
        """Canonical storage dtype name of the value stream (e.g. "float32",
        "int8", "float8_e4m3fn")."""
        return jnp.dtype(self.value.dtype).name

    def tree_flatten(self):
        return (self.value, self.cidx, self.ridx, self.sidx, self.offset,
                self.nnz, self.perm, self.scale), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        value, cidx, ridx, sidx, offset, nnz, perm, scale = leaves
        return cls(value, cidx, ridx, sidx, offset, nnz, shape, perm, scale)


jax.tree_util.register_pytree_node(
    EllConv, EllConv.tree_flatten, EllConv.tree_unflatten)


def ell_k(longest_row: int, pad_to: int = 8) -> int:
    """The ELL row length K of a bank whose longest row holds
    ``longest_row`` nonzeros: rounded up to a multiple of ``pad_to``, and
    at least ``pad_to`` (a fully-pruned bank keeps nonzero-width arrays)."""
    pad_to = max(1, int(pad_to))
    return max(pad_to, -(-max(1, longest_row) // pad_to) * pad_to)


def ell_from_dense_conv(w, pad_to: int = 8, balance: bool = False) -> EllConv:
    """Convert a dense (M, C, R, S) filter bank to ``EllConv``.

    ``pad_to`` rounds K up so jit specialisations are shared across layers with
    similar density (the paper's 'kernel customization' table keys on this).
    K is clamped to ``K >= pad_to >= 1`` even for a fully-pruned (all-zero)
    filter bank, so the Pallas path never sees zero-width value arrays.
    ``balance=True`` additionally sorts output channels by row nnz
    (``balance_ell_conv``) and records the permutation in ``perm``.
    """
    w = np.asarray(w)
    m, c, r, s = w.shape
    if m == 0:
        raise ValueError("ell_from_dense_conv needs at least one output channel")
    pad_to = max(1, int(pad_to))
    rows_val, rows_c, rows_r, rows_s, nnz = [], [], [], [], []
    for i in range(m):
        ci, ri, si = np.nonzero(w[i])
        rows_val.append(w[i, ci, ri, si])
        rows_c.append(ci)
        rows_r.append(ri)
        rows_s.append(si)
        nnz.append(len(ci))
    k = ell_k(max(nnz), pad_to)
    val = np.zeros((m, k), dtype=w.dtype)
    cid = np.zeros((m, k), dtype=np.int32)
    rid = np.zeros((m, k), dtype=np.int32)
    sid = np.zeros((m, k), dtype=np.int32)
    for i in range(m):
        n = nnz[i]
        val[i, :n] = rows_val[i]
        cid[i, :n] = rows_c[i]
        rid[i, :n] = rows_r[i]
        sid[i, :n] = rows_s[i]
    offset = np.zeros((m, k), dtype=np.int32)  # filled by stretch_offsets
    ell = EllConv(
        value=jnp.asarray(val), cidx=jnp.asarray(cid), ridx=jnp.asarray(rid),
        sidx=jnp.asarray(sid), offset=jnp.asarray(offset),
        nnz=jnp.asarray(np.asarray(nnz, np.int32)), shape=(m, c, r, s))
    return balance_ell_conv(ell) if balance else ell


def stretch_offsets(ell: EllConv, hp: int, wp: int) -> EllConv:
    """The paper's *weight stretching*: bake the layout function
    f(c, r, s) = (c*Hp + r)*Wp + s into the column indices, for a padded input
    of spatial shape (Hp, Wp).  Only ``offset`` changes; run once per geometry.
    """
    off = (ell.cidx * hp + ell.ridx) * wp + ell.sidx
    return dataclasses.replace(ell, offset=off.astype(jnp.int32))


def balance_ell_conv(ell: EllConv) -> EllConv:
    """nnz-balanced channel packing: sort output channels by descending row
    nnz (Yao et al., *Balanced Sparsity*, arXiv:1811.00206 — balancing
    nonzeros across parallel workers).

    After sorting, rows of near-equal length sit adjacently, so every TM-tile
    of the Pallas kernel's channel loop holds rows of near-equal nnz instead
    of being bounded by its single worst row.  The permutation is carried in
    ``perm`` (row i of the balanced bank = original channel ``perm[i]``);
    per-row contents are untouched, so each row's accumulation order — and
    therefore its f32 result — is bit-identical to the unbalanced bank's.

    Pure ``jnp`` (stable argsort + row gathers): callable both host-side at
    format-build time and inside a jit trace.  Balancing an already-balanced
    bank composes the permutations (idempotent in effect: the row order is
    already sorted, so the stable argsort is the identity).
    """
    order = jnp.argsort(-ell.nnz, stable=True).astype(jnp.int32)
    take = lambda a: jnp.take(a, order, axis=0)  # noqa: E731
    perm = take(ell.perm) if ell.perm is not None else order
    return EllConv(
        value=take(ell.value), cidx=take(ell.cidx), ridx=take(ell.ridx),
        sidx=take(ell.sidx), offset=take(ell.offset), nnz=take(ell.nnz),
        shape=ell.shape, perm=perm,
        scale=take(ell.scale) if ell.scale is not None else None)


def inverse_permutation(perm: jax.Array) -> jax.Array:
    """Positions of each original row in a permuted bank: if row i of the
    bank is original channel ``perm[i]``, then ``out[:, inv]`` restores
    natural channel order for an output computed in bank row order."""
    return jnp.argsort(perm).astype(jnp.int32)


# ---------------------------------------------------------------------------
# ELL matrix format (sparse linear layers; CSR rectangularised)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class EllMatrix:
    """Sparse (M, N) weight: per row K padded (value, column) pairs."""

    value: jax.Array   # (M, K)
    colidx: jax.Array  # (M, K) int32
    nnz: jax.Array     # (M,) int32
    shape: Tuple[int, int]

    @property
    def k(self) -> int:
        return int(self.value.shape[1])

    def tree_flatten(self):
        return (self.value, self.colidx, self.nnz), self.shape

    @classmethod
    def tree_unflatten(cls, shape, leaves):
        return cls(*leaves, shape=shape)


jax.tree_util.register_pytree_node(
    EllMatrix, EllMatrix.tree_flatten, EllMatrix.tree_unflatten)


def ell_from_dense(w, pad_to: int = 8) -> EllMatrix:
    w = np.asarray(w)
    if w.ndim != 2:
        raise ValueError(f"ell_from_dense expects 2-D, got {w.shape}")
    m, n = w.shape
    if m == 0:
        raise ValueError("ell_from_dense needs at least one row")
    pad_to = max(1, int(pad_to))
    nnz = (w != 0).sum(axis=1)
    k = max(1, int(nnz.max()))
    k = max(pad_to, ((k + pad_to - 1) // pad_to) * pad_to)
    val = np.zeros((m, k), dtype=w.dtype)
    col = np.zeros((m, k), dtype=np.int32)
    for i in range(m):
        (ci,) = np.nonzero(w[i])
        val[i, : len(ci)] = w[i, ci]
        col[i, : len(ci)] = ci
    return EllMatrix(value=jnp.asarray(val), colidx=jnp.asarray(col),
                     nnz=jnp.asarray(nnz.astype(np.int32)), shape=(m, n))


def ell_to_dense(ell: EllMatrix) -> jax.Array:
    """Inverse of ``ell_from_dense`` (oracle for round-trip property tests).

    Padding entries all carry value 0, so scatter-add is safe even though they
    alias column 0.
    """
    m, n = ell.shape
    out = jnp.zeros((m, n), dtype=ell.value.dtype)
    rows = jnp.arange(m)[:, None] * jnp.ones_like(ell.colidx)
    return out.at[rows, ell.colidx].add(ell.value)


# ---------------------------------------------------------------------------
# BCSR (block compressed sparse row) for the MXU path
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BcsrMatrix:
    """Block-sparse (M, N) weight.

    blocks:   (nbr, KB, bm, bn) -- per block-row, KB padded dense tiles
    blockcol: (nbr, KB) int32   -- block-column id of each tile (0 for padding)
    nblocks:  (nbr,) int32      -- true tiles per block-row
    shape:    original (M, N); block: (bm, bn)
    """

    blocks: jax.Array
    blockcol: jax.Array
    nblocks: jax.Array
    shape: Tuple[int, int]
    block: Tuple[int, int]

    @property
    def kb(self) -> int:
        return int(self.blocks.shape[1])

    def tree_flatten(self):
        return (self.blocks, self.blockcol, self.nblocks), (self.shape, self.block)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        shape, block = aux
        return cls(*leaves, shape=shape, block=block)


jax.tree_util.register_pytree_node(
    BcsrMatrix, BcsrMatrix.tree_flatten, BcsrMatrix.tree_unflatten)


def bcsr_from_dense(w, block: Tuple[int, int] = (128, 128), pad_to: int = 1) -> BcsrMatrix:
    """Convert a (block-pruned) dense matrix to BCSR.

    A tile is kept iff it contains any nonzero.  Rows are padded to a common
    tile count KB so shapes are static; padding tiles are all-zero data at
    block-column 0 (inert).  ``pad_to`` rounds KB up (and is clamped to
    ``>= 1`` like the ELL converters), so an all-zero matrix still carries
    one inert tile per block-row instead of a zero-width array.
    """
    w = np.asarray(w)
    m, n = w.shape
    bm, bn = block
    pad_to = max(1, int(pad_to))
    pm, pn = (-m) % bm, (-n) % bn
    wp = np.pad(w, ((0, pm), (0, pn)))
    gm, gn = wp.shape[0] // bm, wp.shape[1] // bn
    tiles = wp.reshape(gm, bm, gn, bn).transpose(0, 2, 1, 3)  # (gm, gn, bm, bn)
    keep = (tiles != 0).any(axis=(2, 3))                      # (gm, gn)
    counts = keep.sum(axis=1)
    kb = max(1, int(counts.max()))
    kb = ((kb + pad_to - 1) // pad_to) * pad_to
    blocks = np.zeros((gm, kb, bm, bn), dtype=w.dtype)
    bcol = np.zeros((gm, kb), dtype=np.int32)
    for i in range(gm):
        (cols,) = np.nonzero(keep[i])
        blocks[i, : len(cols)] = tiles[i, cols]
        bcol[i, : len(cols)] = cols
    return BcsrMatrix(blocks=jnp.asarray(blocks), blockcol=jnp.asarray(bcol),
                      nblocks=jnp.asarray(counts.astype(np.int32)),
                      shape=(m, n), block=block)


def bcsr_to_dense(b: BcsrMatrix) -> jax.Array:
    m, n = b.shape
    bm, bn = b.block
    gm = b.blocks.shape[0]
    gn = (n + bn - 1) // bn
    out = jnp.zeros((gm, gn, bm, bn), dtype=b.blocks.dtype)
    rows = jnp.arange(gm)[:, None] * jnp.ones_like(b.blockcol)
    out = out.at[rows, b.blockcol].add(b.blocks)
    dense = out.transpose(0, 2, 1, 3).reshape(gm * bm, gn * bn)
    return dense[:m, :n]


def bcsr_stack_from_dense(w3d, block: Tuple[int, int] = (128, 128)) -> BcsrMatrix:
    """Convert a stacked (L, M, N) weight to a stacked BCSR (leading L on
    every leaf) so it can ride through a ``lax.scan`` over layers: slicing the
    leading axis of each leaf yields exactly the per-layer ``BcsrMatrix``.
    Rows are padded to the max tile count across all layers."""
    w3d = np.asarray(w3d)
    per_layer = [bcsr_from_dense(w3d[i], block) for i in range(w3d.shape[0])]
    kb = max(b.kb for b in per_layer)
    blocks, bcol, nb = [], [], []
    for b in per_layer:
        pad = kb - b.kb
        blocks.append(np.pad(np.asarray(b.blocks), ((0, 0), (0, pad), (0, 0), (0, 0))))
        bcol.append(np.pad(np.asarray(b.blockcol), ((0, 0), (0, pad))))
        nb.append(np.asarray(b.nblocks))
    return BcsrMatrix(
        blocks=jnp.asarray(np.stack(blocks)), blockcol=jnp.asarray(np.stack(bcol)),
        nblocks=jnp.asarray(np.stack(nb)),
        shape=per_layer[0].shape, block=block)


# ---------------------------------------------------------------------------
# BCSR conv format (blocked filter banks for the MXU conv path)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class BcsrConv:
    """Block-sparse conv weights for an (M, C, R, S) filter bank.

    The bank is viewed as its flattened (M, C*R*S) weight matrix — the same
    matrix ``core/lowering.py`` multiplies against im2col patches — and
    blocked with the :class:`BcsrMatrix` tile/pad machinery: per block-row of
    ``bm`` output channels, a padded list of kept (bm, bn) tiles over the
    flattened input-patch axis.  Column ``j`` of a tile at block-column
    ``bc`` covers the original weight entry ``(c, r, s)`` with
    ``bc*bn + j = c*(R*S) + r*S + s``; columns past ``C*R*S`` (the format's
    right-padding) carry zero weights and are inert.

    blocks:   (gbm, KB, bm, bn) -- per block-row, KB padded dense tiles
    blockcol: (gbm, KB) int32   -- block-column id of each tile (0 = padding)
    nblocks:  (gbm,) int32      -- true tiles per block-row
    shape:    original (M, C, R, S); block: (bm, bn)
    scale:    optional (gbm, bm) f32 -- per-output-channel symmetric
              dequantisation scales of a *quantised* bank
              (:func:`quantize_values`), laid out by (block-row, local row)
              so the kernel can block it like the bias; rows past M (the
              channel padding) carry scale 1 and all-zero values (inert).
              None for banks whose tiles are stored at full width.
    """

    blocks: jax.Array
    blockcol: jax.Array
    nblocks: jax.Array
    shape: Tuple[int, int, int, int]
    block: Tuple[int, int]
    scale: Optional[jax.Array] = None

    @property
    def kb(self) -> int:
        return int(self.blocks.shape[1])

    @property
    def gbm(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def value_dtype(self) -> str:
        """Canonical storage dtype name of the tile data (e.g. "float32",
        "int8", "float8_e4m3fn")."""
        return jnp.dtype(self.blocks.dtype).name

    def tree_flatten(self):
        return ((self.blocks, self.blockcol, self.nblocks, self.scale),
                (self.shape, self.block))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        shape, block = aux
        blocks, blockcol, nblocks, scale = leaves
        return cls(blocks, blockcol, nblocks, shape=shape, block=block,
                   scale=scale)


jax.tree_util.register_pytree_node(
    BcsrConv, BcsrConv.tree_flatten, BcsrConv.tree_unflatten)


def bcsr_conv_from_dense(w, block: Tuple[int, int] = (8, 128),
                         pad_to: int = 1) -> BcsrConv:
    """Convert a dense (M, C, R, S) filter bank to :class:`BcsrConv`.

    Delegates to :func:`bcsr_from_dense` on the flattened (M, C*R*S) weight
    matrix, so the tile-keep rule, KB padding and inert zero tiles are
    exactly the linear-layer BCSR ones.  Weights pruned at tile granularity
    (``core.pruning.block_prune_conv``) yield genuinely sparse block rows;
    unstructured-pruned weights degrade gracefully to a dense blocked bank
    (every tile kept) — slower, never wrong.
    """
    w = np.asarray(w)
    if w.ndim != 4:
        raise ValueError(f"bcsr_conv_from_dense expects 4-D, got {w.shape}")
    m, c, r, s = w.shape
    flat = bcsr_from_dense(w.reshape(m, c * r * s), block, pad_to=pad_to)
    return BcsrConv(blocks=flat.blocks, blockcol=flat.blockcol,
                    nblocks=flat.nblocks, shape=(m, c, r, s), block=block)


def bcsr_conv_to_dense(b: BcsrConv) -> jax.Array:
    """Inverse of ``bcsr_conv_from_dense`` (round-trip / parity oracle).

    A quantised bank reconstructs its *semantic* (dequantised f32) weights —
    dense reconstruction is how the oracles and fallbacks consume the bank.
    """
    if b.scale is not None:
        b = dequantize(b)
    m, c, r, s = b.shape
    flat = BcsrMatrix(blocks=b.blocks, blockcol=b.blockcol,
                      nblocks=b.nblocks, shape=(m, c * r * s), block=b.block)
    return bcsr_to_dense(flat).reshape(m, c, r, s)


# ---------------------------------------------------------------------------
# Quantised value streams (int8 / fp8 banks with per-channel f32 scales)
# ---------------------------------------------------------------------------

# Largest magnitude each narrow storage dtype can carry: int8 keeps the
# symmetric [-127, 127] range (never -128, so negation round-trips), fp8
# e4m3fn's max finite value is 448 (the format has no inf; casts saturate).
QUANT_DTYPES = {"int8": 127.0, "float8_e4m3fn": 448.0}


def _quant_scales(absmax: jax.Array, qmax: float) -> jax.Array:
    """Per-channel symmetric scale mapping |w| <= absmax onto [-qmax, qmax].
    All-zero channels get scale 1 so they quantise — and dequantise — to
    exact zeros instead of dividing by zero."""
    absmax = absmax.astype(jnp.float32)
    return jnp.where(absmax > 0, absmax / qmax, 1.0)


def _storage_dtype(value_dtype: str):
    if value_dtype not in QUANT_DTYPES:
        raise ValueError(
            f"unsupported quantised value dtype {value_dtype!r}; "
            f"expected one of {sorted(QUANT_DTYPES)}")
    return jnp.dtype(value_dtype)


def _quantize_array(w: jax.Array, scale: jax.Array, value_dtype: str):
    """Quantise ``w`` (already broadcast-divided by ``scale``) to storage."""
    q = w.astype(jnp.float32) / scale
    if value_dtype == "int8":
        return jnp.clip(jnp.rint(q), -127, 127).astype(jnp.int8)
    return q.astype(jnp.dtype(value_dtype))


def quantize_values(fmt, value_dtype: str = "int8"):
    """Quantise a conv bank's value stream to ``int8`` or ``float8_e4m3fn``.

    Per-output-channel *symmetric* quantisation: channel m's scale is
    ``absmax_m / 127`` (int8) or ``absmax_m / 448`` (fp8), values are stored
    narrow and the f32 scales ride in ``.scale``; the semantic weight is
    ``value * scale`` (for ``BcsrConv``, tile row ``i`` of block-row ``mt``
    uses ``scale[mt, i]``).  The padding entries of either format are zero
    and stay zero.  Quantising an already-quantised bank raises.

    Round-trip error bounds (``dequantize(quantize_values(b)) - b``), per
    channel with scale ``s`` and original weight ``w``:

    * int8 -- round-to-nearest on ``w / s`` in [-127, 127], so
      ``|err| <= s / 2`` (= ``absmax / 254``) elementwise.
    * float8_e4m3fn -- 3 mantissa bits round-to-nearest: relative error
      ``<= 2**-4`` for normal quotients, absolute error ``<= s * 2**-10``
      below the subnormal threshold; combined
      ``|err| <= max(|w| * 2**-4, s * 2**-10)`` (up to f32 rounding of the
      ``w / s`` quotient itself).

    ``test_sparse_formats.py`` property-checks both bounds.
    """
    _storage_dtype(value_dtype)
    qmax = QUANT_DTYPES[value_dtype]
    if isinstance(fmt, EllConv):
        if fmt.scale is not None:
            raise ValueError("bank is already quantised")
        scale = _quant_scales(jnp.abs(fmt.value).max(axis=1), qmax)
        value = _quantize_array(fmt.value, scale[:, None], value_dtype)
        return dataclasses.replace(fmt, value=value, scale=scale)
    if isinstance(fmt, BcsrConv):
        if fmt.scale is not None:
            raise ValueError("bank is already quantised")
        # (gbm, KB, bm, bn) -> per-(block-row, local-row) channel absmax
        scale = _quant_scales(jnp.abs(fmt.blocks).max(axis=(1, 3)), qmax)
        blocks = _quantize_array(
            fmt.blocks, scale[:, None, :, None], value_dtype)
        return dataclasses.replace(fmt, blocks=blocks, scale=scale)
    raise TypeError(f"quantize_values expects EllConv or BcsrConv, "
                    f"got {type(fmt).__name__}")


def dequantize(fmt):
    """Rebuild the f32 value stream of a quantised bank (``value * scale``).
    Unquantised banks pass through unchanged.  The multiply matches the
    kernels' in-register dequantisation exactly — same operands, same f32
    op — so the ELL kernel run on a quantised bank is bit-identical to the
    f32 kernel run on the dequantised bank."""
    if isinstance(fmt, EllConv):
        if fmt.scale is None:
            return fmt
        value = fmt.value.astype(jnp.float32) * fmt.scale[:, None]
        return dataclasses.replace(fmt, value=value, scale=None)
    if isinstance(fmt, BcsrConv):
        if fmt.scale is None:
            return fmt
        blocks = fmt.blocks.astype(jnp.float32) * fmt.scale[:, None, :, None]
        return dataclasses.replace(fmt, blocks=blocks, scale=None)
    raise TypeError(f"dequantize expects EllConv or BcsrConv, "
                    f"got {type(fmt).__name__}")


def csr_arrays_from_dense(w) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Classic CSR triplet (value, colidx, rowptr) — Fig. 4 of the paper.

    Used by the lowered CUSPARSE-analogue baseline and by format round-trip
    tests; not consumed by jit code (ragged).
    """
    w = np.asarray(w)
    m, _ = w.shape
    rowptr = np.zeros(m + 1, dtype=np.int32)
    vals, cols = [], []
    for i in range(m):
        (ci,) = np.nonzero(w[i])
        vals.append(w[i, ci])
        cols.append(ci.astype(np.int32))
        rowptr[i + 1] = rowptr[i] + len(ci)
    value = np.concatenate(vals) if vals else np.zeros(0, w.dtype)
    colidx = np.concatenate(cols) if cols else np.zeros(0, np.int32)
    return value, colidx, rowptr
