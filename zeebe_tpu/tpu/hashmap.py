"""HBM-resident open-addressing hash table: int64 key → int32 slot.

TPU-native analogue of the reference's off-heap hash maps
(``zb-map/src/main/java/io/zeebe/map/ZbMap.java:37`` — Long2Long maps over
bucket buffer arrays): the table is three device arrays (the keys' low and
high 32-bit words, vals), capacity a power of two, linear probing, batched
vectorized operations. The keys are full 64-bit values held as two int32
planes: a TPU has no 64-bit integers, and an ``int64[T]`` leaf is split and
recombined, whole, at every program boundary it crosses. Query keys arrive
as ``[B] int64`` (a wave) or as ``[B, 2] int32`` (lo, hi) plane rows (a
table's own key column, at a rebuild); 64-bit values exist at wave size
only:

- ``lookup``: gather-probe loop, all queries in parallel.
- ``insert``: deterministic parallel claims — per probe round, each pending
  insert scatters its batch rank onto its candidate bucket with
  ``scatter-min``; the unique winner writes, losers advance their probe.
  Assumes batch keys are unique (engine keys are monotone counters).
- ``delete``: probe to the key's bucket, write a tombstone.

Tombstones keep probe chains intact; the engine rebuilds the table
(``rebuild_from``) when live+dead load crosses ``REBUILD_LOAD`` — the
analogue of ZbMap's block splitting/shrinking (``ZbMap.java:45``).

All ops are jit-compatible and deterministic (scatter conflicts resolved by
batch rank, never by scheduling).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# sentinels as 64-bit values; as (lo, hi) planes EMPTY = (-1, -1) and
# TOMBSTONE = (-2, -1). A real key is non-negative, so its high word is:
# a key whose LOW word reads -1 or -2 is still told apart by the high one.
EMPTY = -1
TOMBSTONE = -2
MAX_PROBES = 32
REBUILD_LOAD = 0.45

_BIG = jnp.iinfo(jnp.int32).max


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["keys_lo", "keys_hi", "vals"], meta_fields=[],
)
@dataclasses.dataclass
class HashTable:
    keys_lo: jax.Array  # [T] int32: the keys' low words
    keys_hi: jax.Array  # [T] int32: the high words; < 0 = EMPTY / TOMBSTONE
    vals: jax.Array  # [T] int32

    @property
    def size(self) -> int:
        return self.keys_lo.shape[0]


def make(capacity: int) -> HashTable:
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return HashTable(
        keys_lo=jnp.full((capacity,), EMPTY, dtype=jnp.int32),
        keys_hi=jnp.full((capacity,), -1, dtype=jnp.int32),
        vals=jnp.zeros((capacity,), dtype=jnp.int32),
    )


def host_keys(table: HashTable) -> np.ndarray:
    """The keys as the ``[T] int64`` array a snapshot holds (host side)."""
    words = np.stack([np.asarray(table.keys_lo), np.asarray(table.keys_hi)], -1)
    return np.ascontiguousarray(words).view(np.int64)[..., 0]


def from_host(keys64, vals) -> HashTable:
    """The inverse: a table from a snapshot's int64 keys and its vals."""
    words = np.ascontiguousarray(keys64, np.int64)[..., None].view(np.int32)
    return HashTable(
        keys_lo=jnp.asarray(words[..., 0]), keys_hi=jnp.asarray(words[..., 1]),
        vals=jnp.asarray(vals),
    )


def _is_empty(lo, hi):
    # an EMPTY bucket terminates a probe chain; a TOMBSTONE does not
    return (lo == EMPTY) & (hi == -1)


def split_keys(keys: jax.Array):
    """Query keys → their (lo, hi) int32 words: ``[B] int64``, or ``[B, 2]
    int32`` plane rows as the state's tables hold them."""
    if not (keys.ndim == 2 and keys.dtype == jnp.int32):
        keys = lax.bitcast_convert_type(keys.astype(jnp.int64), jnp.int32)
    return keys[:, 0], keys[:, 1]


def _hash(lo: jax.Array, hi: jax.Array, table_size: int) -> jax.Array:
    # Multiplicative hash over the two 32-bit halves. TPUs have no native
    # 64-bit multiply (XLA emulates it with 32-bit mul chains — it showed
    # up in every probe-loop fusion); two u32 multiplies are native-cheap
    # and mix just as well for monotone-counter keys.
    lo = lo.astype(jnp.uint32)
    hi = hi.astype(jnp.uint32)
    h = lo * jnp.uint32(0x9E3779B1) ^ hi * jnp.uint32(0x85EBCA77)
    h = h ^ (h >> jnp.uint32(15))
    return (h & jnp.uint32(table_size - 1)).astype(jnp.int32)


def lookup(table: HashTable, keys: jax.Array, valid: jax.Array):
    """Batched lookup. Returns (found [B] bool, vals [B] i32)."""
    table_size = table.size
    klo, khi = split_keys(keys)
    h0 = _hash(klo, khi, table_size)

    def cond(carry):
        i, _, _, done = carry
        # early exit: at sane load factors chains are 1-3 buckets long, and
        # each probe round is a full gather pass — don't run all MAX_PROBES
        return (i < MAX_PROBES) & jnp.any(~done)

    def body(carry):
        i, found, vals, done = carry
        idx = (h0 + i) & (table_size - 1)
        tlo, thi = table.keys_lo[idx], table.keys_hi[idx]
        hit = (~done) & (tlo == klo) & (thi == khi)
        found = found | hit
        vals = jnp.where(hit, table.vals[idx], vals)
        done = done | hit | _is_empty(tlo, thi)
        return i + 1, found, vals, done

    found = jnp.zeros(klo.shape, dtype=bool)
    vals = jnp.full(klo.shape, -1, dtype=jnp.int32)
    done = ~valid
    _, found, vals, _ = lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), found, vals, done)
    )
    return found, vals


def insert(table: HashTable, keys: jax.Array, vals: jax.Array, valid: jax.Array):
    """Batched insert of unique keys. Returns (table', inserted [B] bool).

    ``inserted`` is False for entries that could not be placed within
    MAX_PROBES (over-full table) — the engine must rebuild larger then.
    """
    table_size = table.size
    klo, khi = split_keys(keys)
    batch = klo.shape[0]
    vals = vals.astype(jnp.int32)
    h0 = _hash(klo, khi, table_size)
    rank = jnp.arange(batch, dtype=jnp.int32)

    def cond(carry):
        i, _, _, _, pending, _ = carry
        return (i < MAX_PROBES) & jnp.any(pending)

    def body(carry):
        i, tlo, thi, tvals, pending, probe = carry
        idx = (h0 + probe) & (table_size - 1)
        # claim EMPTY *or* TOMBSTONE buckets (standard open addressing):
        # delete-heavy tables (parallel joins insert+delete per instance)
        # otherwise fill with tombstones until no bucket is claimable and
        # inserts silently fail mid-workload
        # (both sentinels, and nothing else that is stored, have a
        # negative high word)
        free = thi[idx] < 0
        attempt = pending & free
        # deterministic bucket claim: lowest batch rank wins
        order = jnp.where(attempt, rank, _BIG)
        claims = jnp.full((table_size,), _BIG, dtype=jnp.int32).at[idx].min(
            order, mode="drop"
        )
        win = attempt & (claims[idx] == rank)
        widx = jnp.where(win, idx, table_size)
        tlo = tlo.at[widx].set(klo, mode="drop")
        thi = thi.at[widx].set(khi, mode="drop")
        tvals = tvals.at[widx].set(vals, mode="drop")
        pending = pending & ~win
        probe = jnp.where(pending, probe + 1, probe)
        return i + 1, tlo, thi, tvals, pending, probe

    probe = jnp.zeros((batch,), dtype=jnp.int32)
    _, tlo, thi, tvals, pending, _ = lax.while_loop(
        cond, body,
        (jnp.zeros((), jnp.int32), table.keys_lo, table.keys_hi, table.vals,
         valid, probe),
    )
    return HashTable(tlo, thi, tvals), valid & ~pending


def delete(table: HashTable, keys: jax.Array, valid: jax.Array) -> HashTable:
    """Batched delete: the key's bucket becomes a tombstone."""
    table_size = table.size
    klo, khi = split_keys(keys)
    h0 = _hash(klo, khi, table_size)

    def cond(carry):
        i, _, done = carry
        return (i < MAX_PROBES) & jnp.any(~done)

    def body(carry):
        i, slot, done = carry
        idx = (h0 + i) & (table_size - 1)
        tlo, thi = table.keys_lo[idx], table.keys_hi[idx]
        hit = (~done) & (tlo == klo) & (thi == khi)
        slot = jnp.where(hit, idx, slot)
        done = done | hit | _is_empty(tlo, thi)
        return i + 1, slot, done

    slot = jnp.full(klo.shape, table_size, dtype=jnp.int32)
    _, slot, _ = lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), slot, ~valid)
    )
    # TOMBSTONE = (-2, -1); the high word of a stored key was >= 0
    tlo = table.keys_lo.at[slot].set(TOMBSTONE, mode="drop")
    thi = table.keys_hi.at[slot].set(-1, mode="drop")
    return HashTable(tlo, thi, table.vals)


def rebuild_from(capacity: int, keys: jax.Array, vals: jax.Array, valid: jax.Array):
    """Fresh table from live entries (tombstone purge / growth).

    Returns (table, all_inserted bool scalar).
    """
    table = make(capacity)
    table, inserted = insert(table, keys, vals, valid)
    return table, jnp.all(inserted == valid)


def fill_counts(table: HashTable):
    """(live, dead) bucket counts — host uses these to decide on rebuilds."""
    live = jnp.sum(table.keys_hi >= 0)
    dead = jnp.sum((table.keys_hi < 0) & (table.keys_lo == TOMBSTONE))
    return live, dead
