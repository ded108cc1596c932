"""Sorted uint64 keys held as two uint32 planes: the high and low limbs.

The TPU has no 64-bit integer unit.  XLA's x64 rewrite turns every u64
operand of a program into two u32 halves where it enters the program:
the whole array, on every call, however few of its words the program
then reads.  A table kept resident as :class:`LimbTable` skips that
rewrite.  The bounded searches (:mod:`repro.core.search`) gather one
word from each plane and compare limbs lexicographically, which is
exact: ``(hi, lo) <= (qhi, qlo)`` iff ``hi < qhi or (hi == qhi and
lo <= qlo)``.

Two planes, not one ``u32[..., 2]`` array: TPU tiling pads a minor
dimension of 2 to 128 lanes.

On the host the table reads as u64 keys: ``np.asarray(t)`` (and so
``np.asarray(t[s][:count])``) combines the planes in numpy.

Example::

    t = LimbTable.from_u64(np.stack(rows))   # host split, two planes placed
    t.shape                                  # (n_rows, m), as the u64 stack
    np.asarray(t[1])                         # row 1 as uint64, on the host
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

_LO_MASK = 0xFFFFFFFF


class LimbTable:
    """uint64 keys as a pytree of two same-shape uint32 planes.

    Leaves are ``hi`` and ``lo``, so ``jit``, ``vmap``, ``shard_map``
    (one ``PartitionSpec`` covers both) and ``device_put`` treat it as
    any other pytree.  Indexing indexes both planes and stays a
    ``LimbTable``; comparisons between two of them are the exact limb
    compares the searches use."""

    __slots__ = ("hi", "lo")

    def __init__(self, hi, lo):
        self.hi = hi
        self.lo = lo

    # -- construction -------------------------------------------------------
    @classmethod
    def from_u64(cls, keys) -> "LimbTable":
        """Split u64 keys on the host with numpy and place only the two
        planes: no u64 copy of ``keys`` reaches the device.  The split
        is a view of each key's two little-endian words, with no
        arithmetic and no table-sized temporaries."""
        k = np.asarray(keys, dtype="<u8")
        flat = np.ascontiguousarray(k.reshape(-1))
        words = flat.view("<u4").reshape(*k.shape, 2)  # [..., 0] is the low word
        return cls(jnp.asarray(words[..., 1]), jnp.asarray(words[..., 0]))

    @classmethod
    def split(cls, keys) -> "LimbTable":
        """Split u64 keys inside a program (traceable): for query-sized
        operands and single rows written by a device program."""
        k = jnp.asarray(keys, dtype=jnp.uint64)
        hi = (k >> jnp.uint64(32)).astype(jnp.uint32)
        lo = (k & jnp.uint64(_LO_MASK)).astype(jnp.uint32)
        return cls(hi, lo)

    # -- array-like surface ---------------------------------------------------
    @property
    def shape(self) -> tuple:
        return tuple(self.hi.shape)

    def __getitem__(self, idx) -> "LimbTable":
        return LimbTable(self.hi[idx], self.lo[idx])

    def take(self, idx) -> "LimbTable":
        """Clipped gather from both planes (the searches' ``_take``)."""
        return LimbTable(jnp.take(self.hi, idx, mode="clip"), jnp.take(self.lo, idx, mode="clip"))

    def set_row(self, i, row: "LimbTable") -> "LimbTable":
        """Functional ``.at[i].set(row)`` on both planes."""
        return LimbTable(self.hi.at[i].set(row.hi), self.lo.at[i].set(row.lo))

    def combine(self):
        """The keys as one u64 array, inside a program (traceable)."""
        return (self.hi.astype(jnp.uint64) << jnp.uint64(32)) | self.lo.astype(jnp.uint64)

    def __array__(self, dtype=None, copy=None):
        hi = np.asarray(self.hi).astype(np.uint64)
        keys = np.asarray((hi << np.uint64(32)) | np.asarray(self.lo).astype(np.uint64))
        return keys if dtype is None else keys.astype(dtype)

    # -- exact limb compares (elementwise, broadcasting) -----------------------
    def __le__(self, other: "LimbTable"):
        return (self.hi < other.hi) | ((self.hi == other.hi) & (self.lo <= other.lo))

    def __lt__(self, other: "LimbTable"):
        return (self.hi < other.hi) | ((self.hi == other.hi) & (self.lo < other.lo))

    def __eq__(self, other: "LimbTable"):
        return (self.hi == other.hi) & (self.lo == other.lo)

    def __repr__(self):
        return f"LimbTable(shape={self.shape})"

    # -- pytree protocol --------------------------------------------------------
    def tree_flatten(self):
        return (self.hi, self.lo), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


jax.tree_util.register_pytree_node_class(LimbTable)


def keys_of(table, q):
    """``q`` in the form of ``table``'s keys: its limbs when the table is
    a :class:`LimbTable`, else ``q`` itself."""
    return LimbTable.split(q) if isinstance(table, LimbTable) else q
