"""The one traffic generator: reads a mix's parameters, makes its ticks on
the device from ``--seed`` during set-up.

One item kind, ``token_chains``, a port to the device of
``TokenDriftStream`` in ``src/repro/data/streams.py`` (copied, not imported,
so that no change to the program moves the yardstick): each item is a
fixed-length token sequence drawn from one of two bigram "languages" (a
transition table with ``branching`` successors per token); the language
flips every ``flip_every`` ticks.

Rows are identified by a 64-bit hash of their bits (:func:`row_hash`), so a
comparison can ask whether a stored row is one that was offered.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, seed >> 32), salt)


# ---------------------------------------------------------------------------
# token chains (TokenDriftStream on the device)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("ticks", "per_tick", "seq_len",
                                             "vocab", "branching",
                                             "flip_every"))
def token_chains(key, *, ticks: int, per_tick: int, seq_len: int, vocab: int,
                 branching: int, flip_every: int) -> jax.Array:
    """Ticks [ticks, per_tick, seq_len] int32 of bigram-chain sequences."""
    k_trans, k_first, k_pick = jax.random.split(key, 3)
    trans = jax.random.randint(k_trans, (2, vocab, branching), 0, vocab,
                               jnp.int32)
    mode = (jnp.arange(ticks) // flip_every) % 2                # [ticks]
    first = jax.random.randint(k_first, (ticks, per_tick), 0, vocab,
                               jnp.int32)
    picks = jax.random.randint(k_pick, (seq_len - 1, ticks, per_tick), 0,
                               branching, jnp.int32)

    def nxt(prev, pick):
        tok = trans[mode[:, None], prev, pick]
        return tok, tok

    _, rest = jax.lax.scan(nxt, first, picks)                   # [S-1,t,b]
    return jnp.concatenate([first[None], rest], 0).transpose(1, 2, 0)


# ---------------------------------------------------------------------------
# row identity
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("lead",))
def row_hash(tree, *, lead: int) -> jax.Array:
    """[*lead dims, 2] uint32: a 64-bit hash of each row's bits, where a row
    is everything past the first ``lead`` axes of every leaf."""
    parts = []
    for leaf in jax.tree_util.tree_leaves(tree):
        w = jax.lax.bitcast_convert_type(jnp.asarray(leaf), jnp.uint32)
        parts.append(w.reshape(w.shape[:lead] + (-1,)))
    words = jnp.concatenate(parts, axis=-1)
    n = words.shape[-1]
    i = jnp.arange(n, dtype=jnp.uint32)
    m1 = (i * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B9)) | jnp.uint32(1)
    m2 = (i * jnp.uint32(2246822519) + jnp.uint32(0x85EBCA6B)) | jnp.uint32(1)

    def mix(m):
        h = words * m
        h = h ^ (h >> jnp.uint32(15))
        h = h * jnp.uint32(0x2C1B3C6D)
        return jnp.sum(h ^ (h >> jnp.uint32(12)), axis=-1, dtype=jnp.uint32)

    return jnp.stack([mix(m1), mix(m2)], axis=-1)


def hash64(h: np.ndarray) -> np.ndarray:
    """[..., 2] uint32 -> [...] uint64."""
    h = np.asarray(h).astype(np.uint64)
    return (h[..., 0] << np.uint64(32)) | h[..., 1]
