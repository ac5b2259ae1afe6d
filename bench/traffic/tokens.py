"""Step-indexed training batches drawn from the seed.

A copy of the program's synthetic `TokenPipeline`, kept with the benchmark
so that no later change to the program can make the feed cheaper:
`batch(step)` is a pure function of (seed, step), so a run that rolls back
and replays a step sees the same rows again. Tokens follow a squared
uniform (low ids frequent); every even position repeats its neighbour
shifted by one, with 10% of positions re-drawn; labels are the next token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


class TokenFeed:
    def __init__(self, key, vocab_size: int, global_batch: int,
                 seq_len: int):
        self.key = key
        self.vocab_size = vocab_size
        self.global_batch = global_batch
        self.seq_len = seq_len
        self._batch = jax.jit(functools.partial(
            _batch, vocab=vocab_size, rows=global_batch, seq=seq_len))

    def batch(self, step: int) -> dict:
        return self._batch(self.key, jnp.int32(step))


def _batch(key, step, *, vocab: int, rows: int, seq: int) -> dict:
    k1, k2 = jax.random.split(jax.random.fold_in(key, step))
    u = jax.random.uniform(k1, (rows, seq + 1))
    base = (u * u * (vocab - 1)).astype(jnp.int32)
    idx = jnp.arange(seq + 1)
    repeat = jnp.roll(base, 1, axis=1) + 1
    toks = jnp.where((idx % 2 == 0)[None, :], base, repeat % vocab)
    drop = jax.random.bernoulli(k2, 0.1, toks.shape)
    toks = jnp.where(drop, base, toks)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
