"""Dispatch wrapper for the checkpoint checksum.

`leaf_checksum` routes each leaf to the cheapest correct implementation:

  - host numpy arrays       → vectorized numpy reference (no tobytes copy)
  - jax arrays on one TPU   → Pallas tiled-reduction kernel (on-device)
  - other jax arrays        → jitted jnp reduction (same math, same wrap);
                              this includes arrays sharded over a mesh

All three compute the identical (s0, s1) word-sum pair defined in
`ref.py`; parity is asserted in tests/test_checksum.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .ref import (MIX_MULS, TILE_WORDS, checksum_words_ref,
                  tile_checksums_ref)

# Below this many words a kernel launch costs more than it saves.
_PALLAS_MIN_WORDS = 1 << 15


def _pallas_path(n_words: int, sharding) -> bool:
    """The Pallas kernels run on a TPU, for streams long enough to pay
    for a launch, held by one device: a Mosaic kernel cannot be
    partitioned over a mesh, so a sharded or replicated stream takes the
    jnp reduction, which XLA partitions."""
    return (jax.default_backend() == "tpu"
            and n_words >= _PALLAS_MIN_WORDS
            and len(sharding.device_set) == 1)


def digest_bytes(x: jax.Array) -> tuple[int, int]:
    """(bytes of `x`'s word stream, the part of them that the device
    digests hand to a Pallas kernel), from the shape alone: the stream
    is zero-padded to whole words."""
    nbytes = 4 * -(-x.size * x.dtype.itemsize // 4)
    return nbytes, nbytes if _pallas_path(nbytes // 4, x.sharding) else 0


def device_digestible(x) -> bool:
    """True when the device path can turn `x` into a word stream (every
    dtype of 1, 2, 4 or 8 bytes); others digest on the host."""
    return x.dtype.itemsize in (1, 2, 4, 8)


def _device_words(x: jax.Array) -> jax.Array:
    """Bitcast a device array to its little-endian uint32 word stream,
    zero-padded to a whole number of words (matches ref._byte_view)."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    flat = x.reshape(-1)
    isz = x.dtype.itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32)
    if isz == 8:
        return jax.lax.bitcast_convert_type(flat, jnp.uint32).reshape(-1)
    if isz == 2:
        u16 = jax.lax.bitcast_convert_type(flat, jnp.uint16)
        if u16.size % 2:
            u16 = jnp.concatenate([u16, jnp.zeros((1,), jnp.uint16)])
        pairs = u16.reshape(-1, 2).astype(jnp.uint32)
        return pairs[:, 0] | (pairs[:, 1] << 16)
    if isz == 1:
        u8 = jax.lax.bitcast_convert_type(flat, jnp.uint8)
        pad = -u8.size % 4
        if pad:
            u8 = jnp.concatenate([u8, jnp.zeros((pad,), jnp.uint8)])
        quads = u8.reshape(-1, 4).astype(jnp.uint32)
        return (quads[:, 0] | (quads[:, 1] << 8)
                | (quads[:, 2] << 16) | (quads[:, 3] << 24))
    raise TypeError(f"unsupported itemsize {isz} for dtype {x.dtype}")


@jax.jit
def _wordsum_jnp(words):
    idx = jnp.arange(1, words.size + 1, dtype=jnp.uint32)
    s0 = jnp.sum(words, dtype=jnp.uint32)
    s1 = jnp.sum(words * idx, dtype=jnp.uint32)
    return s0, s1


def checksum_words(x, *, interpret: bool = False) -> tuple[int, int]:
    """(s0, s1) of an array's byte stream via the device path.

    `x` must be a jax array (or convertible); use `checksum_words_ref`
    for the pure-host path. `interpret=True` forces the Pallas kernel in
    interpret mode (for CPU parity testing).
    """
    words = _device_words(jnp.asarray(x))
    if words.size == 0:
        return 0, 0
    if interpret or _pallas_path(words.size, words.sharding):
        # lazy: host-only digest paths never pay the pallas import
        from .kernel import checksum_kernel
        s0, s1 = checksum_kernel(words, interpret=interpret)
    else:
        s0, s1 = _wordsum_jnp(words)
    return int(s0), int(s1)


def checksum_words_device(x: jax.Array):
    """Like checksum_words but returns the (s0, s1) *device scalars*
    without forcing a host sync — the async checkpoint path enqueues the
    reduction alongside the D2H drain and int()s the result on the
    writer thread. Returns None for empty arrays (checksum (0, 0))."""
    words = _device_words(jnp.asarray(x))
    if words.size == 0:
        return None
    if _pallas_path(words.size, words.sharding):
        from .kernel import checksum_kernel
        return checksum_kernel(words)
    return _wordsum_jnp(words)


@jax.jit
def _tilesum_jnp(words):
    n = words.size
    nt = max(1, -(-n // TILE_WORDS))
    w = jnp.pad(words, (0, nt * TILE_WORDS - n)).reshape(nt, TILE_WORDS)
    idx = jnp.arange(1, TILE_WORDS + 1, dtype=jnp.uint32)
    mixed = (w ^ (w >> jnp.uint32(16))) * jnp.uint32(MIX_MULS[0])
    mixed = (mixed ^ (mixed >> jnp.uint32(13))) * jnp.uint32(MIX_MULS[1])
    mixed = mixed ^ (mixed >> jnp.uint32(16))
    s0 = jnp.sum(w, axis=1, dtype=jnp.uint32)
    s1 = jnp.sum(w * idx, axis=1, dtype=jnp.uint32)
    m = jnp.sum(mixed, axis=1, dtype=jnp.uint32)
    return jnp.stack([s0, s1, m], axis=1)


def tile_checksums_device(x, *, interpret: bool = False):
    """Per-4KB-tile (s0, s1, mix) digests of a device array, computed on
    device and returned as *device* (n_tiles, 3) uint32 — the delta
    checkpoint path enqueues this alongside the D2H drain and
    np.asarray()s the tiny result (12 B/tile) on the writer thread.
    Returns None for empty arrays. Same values as `tile_checksums_ref`
    (parity-tested)."""
    words = _device_words(jnp.asarray(x))
    if words.size == 0:
        return None
    if interpret or _pallas_path(words.size, words.sharding):
        from .kernel import tile_checksum_kernel
        return tile_checksum_kernel(words, interpret=interpret)
    return _tilesum_jnp(words)


@jax.jit
def _gather_jnp(tiles2d, idx):
    return jnp.take(tiles2d, idx, axis=0)


def _device_tiles2d(x) -> jax.Array:
    """Device array → its (n_tiles, TILE_WORDS) uint32 tile matrix,
    trailing partial tile zero-padded (same padding as the digest path,
    so tile t here is byte-identical to digest row t's input)."""
    words = _device_words(jnp.asarray(x))
    nt = max(1, -(-words.size // TILE_WORDS))
    return jnp.pad(words, (0, nt * TILE_WORDS - words.size)) \
        .reshape(nt, TILE_WORDS)


def gather_tiles_device(x, idx, *, interpret: bool = False) -> jax.Array:
    """Gather the 4 KB tiles named by `idx` (host int array, ascending)
    from a device array into one compact (len(idx), TILE_WORDS) uint32
    *device* buffer — the delta checkpointer's dirty-tile gather. The
    caller kicks copy_to_host_async on the result, so the D2H transfer
    moves only the dirty tiles (plus 12 B/tile of digest rows), never
    the full state. Parity with `gather_tiles_ref` is tested.
    """
    tiles2d = _device_tiles2d(x)
    idx = jnp.asarray(np.asarray(idx, np.int32))
    if interpret or _pallas_path(tiles2d.size, tiles2d.sharding):
        from .kernel import gather_tiles_kernel
        return gather_tiles_kernel(
            tiles2d.reshape(-1, 128), idx, interpret=interpret)
    return _gather_jnp(tiles2d, idx)


def tile_checksums(arr) -> np.ndarray:
    """Type-dispatching per-tile digest entry point (host ndarray out):
    device arrays stay on device for the reduction, host arrays go through
    the vectorized numpy reference."""
    if isinstance(arr, jax.Array) and device_digestible(arr):
        t = tile_checksums_device(arr)
        return np.zeros((0, 3), np.uint32) if t is None else np.asarray(t)
    return tile_checksums_ref(np.asarray(arr))


def leaf_checksum(arr) -> tuple[int, int]:
    """Type-dispatching entry point used by checkpoint.manifest."""
    if isinstance(arr, jax.Array) and device_digestible(arr):
        return checksum_words(arr)
    return checksum_words_ref(np.asarray(arr))
