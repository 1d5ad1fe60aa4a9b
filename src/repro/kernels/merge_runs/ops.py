"""Device programs for the compaction merge.

The store's merge (:func:`merge_order`) orders two sorted runs of
variable-length byte keys on the accelerator.  Keys travel as a *packed key
column*: a ``(W + 2, cap)`` ``uint32`` array, one column per entry, where

* rows ``0 .. W-1`` hold the key's bytes as big-endian words, zero-padded;
* row ``W`` holds the key length, the last compare word, so a key sorts
  before its zero-extended twin (``b"ab" < b"ab\\x00"``);
* row ``W + 1`` holds the tombstone flag (payload, not compared).

``W`` and ``cap`` are powers of two, so a process compiles a few programs
rather than one per compaction.  Columns past the run's length are padding:
every row is :data:`SENTINEL`, which sorts after any real key because a real
key's length is at most :data:`MAX_KEY_BYTES`.

Each run is sorted with unique keys, so the merge is a merge path: every key
is ranked in the other run by a vectorised binary search, and its output
position is its own index plus that rank (newer before older on equal keys).
No sort runs on the device.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL = np.uint32(0xFFFFFFFF)
MAX_KEY_BYTES = 256   # longest key the packing takes (64 words)
MIN_ROWS = 1024       # smallest row bucket: small runs share one program


def bucket(n: int, floor: int = 1) -> int:
    """Smallest power of two >= max(n, floor)."""
    return 1 << (max(n, floor) - 1).bit_length()


def pack_keys(keys: Sequence[bytes], tombstones: Sequence[bool]) -> np.ndarray:
    """Pack sorted keys into a host copy of a key column (see module doc).

    Raises ``ValueError`` for a key longer than :data:`MAX_KEY_BYTES`.
    """
    n = len(keys)
    longest = max(map(len, keys), default=0)
    if longest > MAX_KEY_BYTES:
        raise ValueError(
            f"key of {longest} bytes exceeds the {MAX_KEY_BYTES}-byte limit of the device merge"
        )
    w = bucket(-(-longest // 4))
    col = np.full((w + 2, bucket(n, MIN_ROWS)), SENTINEL, np.uint32)
    if n:
        buf = b"".join(k.ljust(4 * w, b"\0") for k in keys)
        col[:w, :n] = np.frombuffer(buf, ">u4").reshape(n, w).T
        col[w, :n] = np.fromiter(map(len, keys), np.uint32, n)
        col[w + 1, :n] = np.fromiter(tombstones, bool, n)
    return col


def unpack_keys(column, n: int) -> list[tuple[bytes, bool]]:
    """The ``(key, tombstone)`` pairs of a column's first ``n`` entries.

    Raises ``ValueError`` if a column past ``n`` is not padding.
    """
    col = np.asarray(column)
    w = col.shape[0] - 2
    if not (col[:, n:] == SENTINEL).all():
        raise ValueError(f"key column holds entries past its {n} real ones")
    raw = col[:w, :n].T.astype(">u4").tobytes()
    lens = col[w, :n].tolist()
    return [(raw[4 * w * j : 4 * w * j + lens[j]], bool(t)) for j, t in enumerate(col[w + 1, :n].tolist())]


def _widen(col: jax.Array, w: int) -> jax.Array:
    """Re-pack a column to ``w`` key words (zero words; padding stays SENTINEL)."""
    have = col.shape[0] - 2
    if have == w:
        return col
    fill = jnp.where(col[have] == SENTINEL, SENTINEL, jnp.uint32(0))
    extra = jnp.broadcast_to(fill, (w - have, col.shape[1]))
    return jnp.concatenate([col[:have], extra, col[have:]], axis=0)


def _less(a: jax.Array, b: jax.Array, *, strict: bool) -> jax.Array:
    """Column-wise lexicographic ``a < b`` (or ``a <= b``) over the rows."""
    lt = jnp.zeros(a.shape[1], bool)
    eq = jnp.ones(a.shape[1], bool)
    for r in range(a.shape[0]):
        lt = lt | (eq & (a[r] < b[r]))
        eq = eq & (a[r] == b[r])
    return lt if strict else lt | eq


def _rank(queries: jax.Array, run: jax.Array, *, strict: bool) -> jax.Array:
    """How many columns of the sorted ``run`` are < (or <=) each query."""
    cap = run.shape[1]
    pos = jnp.zeros(queries.shape[1], jnp.int32)
    if cap == 0:
        return pos
    steps = jnp.asarray([1 << s for s in range(cap.bit_length() - 1, -1, -1)], jnp.int32)

    def step(i, pos):
        cand = pos + steps[i]
        probe = jnp.take(run, jnp.minimum(cand, cap) - 1, axis=1)
        ok = (cand <= cap) & _less(probe, queries, strict=strict)
        return jnp.where(ok, cand, pos)

    return jax.lax.fori_loop(0, steps.shape[0], step, pos)


@functools.partial(jax.jit, static_argnames=("out_rows",))
def _merge_order(newer: jax.Array, older: jax.Array, drop_tombstones: jax.Array, *, out_rows: int):
    w = max(newer.shape[0], older.shape[0]) - 2
    newer, older = _widen(newer, w), _widen(older, w)
    cap_n, cap_o = newer.shape[1], older.shape[1]
    kn, ko = newer[: w + 1], older[: w + 1]
    # merge path: the newer entry goes first on equal keys, and padding
    # (all SENTINEL) lands after every real key of both runs
    pos_n = jnp.arange(cap_n, dtype=jnp.int32) + _rank(kn, ko, strict=True)
    le = _rank(ko, kn, strict=False)
    pos_o = jnp.arange(cap_o, dtype=jnp.int32) + le
    valid_n, valid_o = kn[w] != SENTINEL, ko[w] != SENTINEL
    twin = jnp.take(kn, jnp.maximum(le - 1, 0), axis=1)
    shadow_o = valid_o & (le > 0) & jnp.all(twin == ko, axis=0)
    drop_n = drop_tombstones & valid_n & (newer[w + 1] == 1)
    drop_o = drop_tombstones & valid_o & ~shadow_o & (older[w + 1] == 1)
    keep_n, keep_o = valid_n & ~drop_n, valid_o & ~shadow_o & ~drop_o

    total = cap_n + cap_o

    def scatter(a, b, dtype):
        out = jnp.zeros(total, dtype)
        out = out.at[pos_n].set(a.astype(dtype), unique_indices=True, indices_are_sorted=True)
        return out.at[pos_o].set(b.astype(dtype), unique_indices=True, indices_are_sorted=True)

    # one array for the host: source index << 2 | dropped << 1 | shadowed
    order = scatter(jnp.arange(cap_n) << 2, (cap_n + jnp.arange(cap_o)) << 2, jnp.int32)
    order = order | scatter(drop_n, drop_o, jnp.int32) << 1 | scatter(jnp.zeros(cap_n, bool), shadow_o, jnp.int32)
    # the merged level's key column: survivors packed to the front in order
    slot = jnp.cumsum(scatter(keep_n, keep_o, jnp.int32)) - 1
    dst_n = jnp.where(keep_n, slot[pos_n], out_rows)
    dst_o = jnp.where(keep_o, slot[pos_o], out_rows)
    col = jnp.full((w + 2, out_rows), SENTINEL, jnp.uint32)
    col = col.at[:, dst_n].set(newer, mode="drop").at[:, dst_o].set(older, mode="drop")
    return order, col


@dataclasses.dataclass
class MergeOrder:
    """Host view of one device merge of ``newer`` over ``older``.

    ``perm[i]`` indexes ``newer_entries + older_entries`` (padding removed)
    for the i-th entry in merged key order; ``shadowed`` marks older entries
    whose key equals their newer neighbour's; ``dropped`` marks tombstones a
    last-level merge removes.  ``keys`` is the merged level's key column,
    survivors only, still on the device.
    """

    perm: np.ndarray
    shadowed: np.ndarray
    dropped: np.ndarray
    keys: jax.Array


def merge_order(newer: jax.Array, n_newer: int, older: jax.Array, n_older: int,
                *, drop_tombstones: bool) -> MergeOrder:
    """Merge two key columns holding ``n_newer`` / ``n_older`` real entries.

    Raises ``ValueError`` if a column has fewer rows than its run has entries.
    """
    if newer.shape[1] < n_newer or older.shape[1] < n_older:
        raise ValueError(
            f"key columns of {newer.shape[1]} and {older.shape[1]} rows cannot hold runs "
            f"of {n_newer} and {n_older} entries"
        )
    n = n_newer + n_older
    order, keys = _merge_order(newer, older, np.bool_(drop_tombstones), out_rows=bucket(n, MIN_ROWS))
    order = np.asarray(order)[:n]
    perm, cap_n = order >> 2, newer.shape[1]
    perm = np.where(perm >= cap_n, perm - cap_n + n_newer, perm)
    return MergeOrder(perm, (order & 1).astype(bool), (order & 2).astype(bool), keys)


def empty_column() -> np.ndarray:
    """A key column with no rows (the older side of a merge into an empty level)."""
    return np.zeros((3, 0), np.uint32)


def compiled_merge_programs() -> int:
    """How many store-merge programs this process has compiled."""
    return _merge_order._cache_size()

