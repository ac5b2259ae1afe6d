"""Pallas TPU kernels for the checkpoint digests and the dirty-tile gather.

The digests are the (s0, s1) word-sums and per-tile (s0, s1, m) rows of
`ref.py`, defined over a uint32 word stream mod 2^32. The TPU's vector
units reduce signed integers only, so the kernels bitcast the words to
int32 and compute in int32: two's-complement add and multiply wrap mod
2^32 exactly like uint32 arithmetic, so the bits are the same. The
results are bitcast back to uint32 outside the kernel.

Every block obeys the (8, 128) tiling rule and every output is
lane-dense:

  checksum_kernel        walks (block_rows, 128) stripes of the stream on
                         a sequential grid and accumulates per-lane sums
                         in two resident (1, 128) output blocks; XLA folds
                         the 128 lanes afterwards.
  tile_checksum_kernel   one grid step digests 128 tiles of (8, 128)
                         words: sublane sums give (tile, lane) partials,
                         a transpose puts the tiles on lanes, and the lane
                         reduction writes one (8, 128) block whose rows
                         0..2 are the s0, s1, m columns of those tiles.
  gather_tiles_kernel    scalar-prefetched tile indices drive the input
                         index map: grid step i DMAs tile idx[i].

Only the digests (8 B per leaf, 12 B per tile) or the gathered dirty
tiles ever cross back to the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import MIX_MULS, TILE_WORDS

_COLS = 128
_ROWS_PER_TILE = TILE_WORDS // _COLS            # 8
# tiles digested per grid step: puts one tile per lane after the transpose
_TILES_PER_BLOCK = _COLS
_MIX_MULS_I32 = tuple(int(np.uint32(m).view(np.int32)) for m in MIX_MULS)
# tiles per gather call: its prefetched indices (256 KiB) must fit the
# 1 MiB of scalar memory
_GATHER_CHUNK = 1 << 16


def _as_i32(words):
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def _as_u32(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _checksum_kernel(w_ref, s0_ref, s1_ref, *, block_rows: int):
    gi = pl.program_id(0)

    @pl.when(gi == 0)
    def _init():
        s0_ref[...] = jnp.zeros_like(s0_ref)
        s1_ref[...] = jnp.zeros_like(s1_ref)

    w = w_ref[...]                                   # (block_rows, 128)
    row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    idx = (gi * block_rows + row) * _COLS + col + 1  # global word index + 1
    s0_ref[...] += jnp.sum(w, axis=0, keepdims=True)
    s1_ref[...] += jnp.sum(w * idx, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def checksum_kernel(words, *, block_rows: int = 512,
                    interpret: bool = False):
    """words: 1-D uint32 → (s0, s1) uint32 device scalars.

    Zero padding up to whole blocks adds nothing to either sum. The
    block is clamped to the (8-row aligned) stream, so a small leaf runs
    as one grid step."""
    n = words.size
    rows = -(-n // _COLS)
    block_rows = min(block_rows, -(-rows // 8) * 8)
    rows_pad = -(-rows // block_rows) * block_rows
    w2 = _as_i32(jnp.pad(words, (0, rows_pad * _COLS - n))) \
        .reshape(rows_pad, _COLS)
    lanes = pl.BlockSpec((1, _COLS), lambda i: (0, 0))
    s0, s1 = pl.pallas_call(
        functools.partial(_checksum_kernel, block_rows=block_rows),
        grid=(rows_pad // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, _COLS), lambda i: (i, 0))],
        out_specs=[lanes, lanes],
        out_shape=[jax.ShapeDtypeStruct((1, _COLS), jnp.int32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(w2)
    return _as_u32(jnp.sum(s0)), _as_u32(jnp.sum(s1))


def _tile_checksum_kernel(w_ref, out_ref):
    w = w_ref[...]                                   # (tiles, 8, 128)
    row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 2)
    idx = row * _COLS + col + 1                      # word index in tile + 1
    mixed = w
    for shift, mul in zip((16, 13), _MIX_MULS_I32):    # murmur3 finalizer
        mixed = (mixed ^ jax.lax.shift_right_logical(mixed, shift)) * mul
    mixed = mixed ^ jax.lax.shift_right_logical(mixed, 16)
    out_ref[...] = jnp.zeros_like(out_ref)
    for c, v in enumerate((w, w * idx, mixed)):
        part = jnp.sum(v, axis=1)                    # (tile, lane)
        out_ref[c:c + 1, :] = jnp.sum(part.T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tile_checksum_kernel(words, *, interpret: bool = False):
    """words: 1-D uint32 → (n_tiles, 3) uint32 per-4KB-tile digests.

    The tile is TILE_WORDS = 8*128 words, matching `ref.tile_checksums_ref`
    bit-for-bit (trailing partial tile zero-padded). Grid steps are
    independent ("parallel" semantics); only 12 bytes per tile — 0.3% of
    the data — ever leave the device.
    """
    n = words.size
    nt = max(1, -(-n // TILE_WORDS))
    nb = -(-nt // _TILES_PER_BLOCK)
    ntp = nb * _TILES_PER_BLOCK
    w3 = _as_i32(jnp.pad(words, (0, ntp * TILE_WORDS - n))) \
        .reshape(ntp, _ROWS_PER_TILE, _COLS)
    out = pl.pallas_call(
        _tile_checksum_kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((_TILES_PER_BLOCK, _ROWS_PER_TILE, _COLS),
                               lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((8, _TILES_PER_BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, ntp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w3)
    return _as_u32(out[:3, :nt].T)


def _gather_tiles_kernel(idx_ref, in_ref, out_ref):
    """Grid step i copies the one (8, 128) tile block the scalar-
    prefetched index map already DMA'd into VMEM — tile idx[i] of the
    source stream lands at row i of the compact output."""
    out_ref[...] = in_ref[...]


def _gather_call(tiles, idx, interpret: bool):
    k = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(k,),
        in_specs=[pl.BlockSpec((_ROWS_PER_TILE, _COLS),
                               lambda i, idx_ref: (idx_ref[i], 0))],
        out_specs=pl.BlockSpec((_ROWS_PER_TILE, _COLS),
                               lambda i, idx_ref: (i, 0)),
    )
    return pl.pallas_call(
        _gather_tiles_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((k * _ROWS_PER_TILE, _COLS),
                                       jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(idx, tiles)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_tiles_kernel(tiles, idx, *, interpret: bool = False):
    """tiles: (nt*8, 128) uint32 word rows; idx: (k,) int32 ascending
    tile indices → (k, TILE_WORDS) uint32 compact dirty-tile buffer.

    The dirty-tile indices are scalar-prefetched so the input BlockSpec's
    index map can read them: grid step i DMAs exactly the (8, 128) block
    of tile idx[i] from HBM and streams it to output block i. More than
    _GATHER_CHUNK tiles are gathered in several calls. Only the gathered
    tiles ever move — the D2H copy that follows is O(dirt), not O(state).
    """
    k = idx.shape[0]
    parts = [_gather_call(tiles, idx[i:i + _GATHER_CHUNK], interpret)
             for i in range(0, k, _GATHER_CHUNK)]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out.reshape(k, TILE_WORDS)
