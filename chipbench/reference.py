"""Plain numpy reference of what the benchmark checks the router against.

Written from the published descriptions, not from the code under test, and
importing nothing of it:

* BinomialHash (Coluzzi et al., arXiv:2406.19836, Alg. 1 and 2) in the
  32-bit word: murmur3 ``fmix32`` as the hash family, ``omega`` rounds,
  blocks A/B/C as in the paper;
* the replacement table of MementoHash (Coluzzi et al., arXiv:2306.09783):
  a permutation of the slot space whose first ``n_alive`` entries are the
  alive slots; a failure swaps its slot to the boundary.  A key whose
  bucket has failed is redirected at most twice: once over the whole
  position space, and, where that lands on a failed position, once more
  over the alive prefix;
* R-way placement: ``r`` salted key families routed independently, a
  family that collides with an earlier column re-salted into the alive
  prefix and probed linearly for ``r`` positions.

Every function works on numpy arrays of u32 keys, one vectorised pass per
round, so a 2^20-key batch takes a fraction of a second on the host.
"""
from __future__ import annotations

import numpy as np

U32 = np.uint32
GOLDEN = 0x9E3779B9
#: seeds the re-salt chain of a colliding placement column
RESALT = 0x7F4A7C15


def mix32(h) -> np.ndarray:
    """murmur3 fmix32 on a u32 array (wraps mod 2^32)."""
    h = np.array(h, dtype=U32, copy=True)
    h ^= h >> U32(16)
    h *= U32(0x85EBCA6B)
    h ^= h >> U32(13)
    h *= U32(0xC2B2AE35)
    h ^= h >> U32(16)
    return h


def hash_pair(h, f) -> np.ndarray:
    """The two-argument hash of Alg. 2: mix(h xor mix(f + golden))."""
    f = np.asarray(f, dtype=U32) + U32(GOLDEN)
    return mix32(np.asarray(h, dtype=U32) ^ mix32(f))


def mulhi(a, b) -> np.ndarray:
    """floor(a * b / 2^32): maps a uniform u32 onto [0, b)."""
    return ((np.asarray(a, np.uint64) * np.uint64(b)) >> np.uint64(32)).astype(U32)


def relocate(b: np.ndarray, h: np.ndarray) -> np.ndarray:
    """Alg. 2: move ``b`` to a uniformly chosen node of its own tree level."""
    b = np.asarray(b, U32)
    out = b.copy()
    deep = b >= 2
    if deep.any():
        level = np.frexp(b[deep].astype(np.float64))[1] - 1  # floor(log2 b)
        top = (np.uint64(1) << level.astype(np.uint64)).astype(U32)
        f = top - U32(1)
        out[deep] = top + (hash_pair(h[deep], f) & f)
    return out


def binomial(keys, n: int, omega: int) -> np.ndarray:
    """Alg. 1 over a u32 key array: keys -> buckets in [0, n)."""
    keys = np.asarray(keys, U32).reshape(-1)
    if n <= 1:
        return np.zeros(keys.shape, np.int64)
    levels = (n - 1).bit_length()
    E, M = 1 << levels, 1 << (levels - 1)
    h0 = mix32(keys)
    out = np.empty(keys.shape, np.int64)
    pending = np.arange(keys.size)
    h = h0
    for i in range(omega):
        c = relocate(h & U32(E - 1), h)
        minor = c < M  # block A: fold into the minor tree with the first hash
        valid = ~minor & (c < n)  # block B
        done = pending[minor]
        out[done] = relocate(h0[done] & U32(M - 1), h0[done])
        out[pending[valid]] = c[valid]
        pending = pending[~(minor | valid)]
        if not pending.size:
            return out
        h = mix32(keys[pending] + U32(((i + 1) * GOLDEN) & 0xFFFFFFFF))
    out[pending] = relocate(h0[pending] & U32(M - 1), h0[pending])  # block C
    return out


class Fleet:
    """Slot space ``[0, n)`` with failures, kept as the replacement table."""

    def __init__(self, n: int):
        self.slots = np.arange(n, dtype=np.int64)
        self.pos = np.arange(n, dtype=np.int64)
        self.n_alive = n

    @property
    def n_total(self) -> int:
        return self.slots.size

    def fail(self, node: int) -> None:
        """Node ``node`` fails; the last slot cannot (that is a resize)."""
        if node == self.n_total - 1:
            raise ValueError("failing the last slot is a resize, not a failure")
        p = int(self.pos[node])
        if p >= self.n_alive:
            raise ValueError(f"node {node} has already failed")
        last = self.n_alive - 1
        other = int(self.slots[last])
        self.slots[p], self.slots[last] = other, node
        self.pos[other], self.pos[node] = p, last
        self.n_alive -= 1

    def failed(self) -> np.ndarray:
        """(n_total,) bool: slot has failed."""
        mask = np.zeros(self.n_total, bool)
        mask[self.slots[self.n_alive:]] = True
        return mask


def route(keys, fleet: Fleet, omega: int, *, second_redirect: bool = True
          ) -> np.ndarray:
    """Lookup plus the table divert: keys -> alive node ids.

    ``second_redirect=False`` is the benchmark's control: it leaves out the
    redirect over the alive prefix, so a key whose first redirect lands on a
    failed position is answered with a failed node.
    """
    keys = np.asarray(keys, U32).reshape(-1)
    b = binomial(keys, fleet.n_total, omega)
    hit = np.flatnonzero(fleet.failed()[b])
    if hit.size:
        h = hash_pair(keys[hit], b[hit].astype(U32))
        q = mulhi(h, fleet.n_total)
        deep = q >= fleet.n_alive
        if second_redirect and deep.any():
            seed = h[deep] ^ (q[deep] * U32(GOLDEN))
            q[deep] = mulhi(mix32(seed), fleet.n_alive)
        b[hit] = fleet.slots[q]
    return b


def family_salts(r: int) -> list[int]:
    """Per-column salts: (j * 7919 + 1) * golden, in u32."""
    return [(((j * 7919 + 1) & 0xFFFFFFFF) * GOLDEN) & 0xFFFFFFFF
            for j in range(r)]


def place(keys, fleet: Fleet, r: int, omega: int, *, resalt: bool = True
          ) -> np.ndarray:
    """Place each key on ``r`` distinct alive nodes: (N, r) node ids.

    ``resalt=False`` is the benchmark's control: collisions between columns
    stand, so a key may hold fewer than ``r`` distinct nodes.
    """
    keys = np.asarray(keys, U32).reshape(-1)
    out = np.empty((keys.size, r), np.int64)
    for j, salt in enumerate(family_salts(r)):
        fam = mix32(keys ^ U32(salt))
        col = route(fam, fleet, omega)
        if j and resalt:
            taken = (col[:, None] == out[:, :j]).any(axis=1)
            q = mulhi(mix32(fam ^ U32(RESALT)), fleet.n_alive).astype(np.int64)
            for _ in range(r):
                cand = fleet.slots[q]
                free = taken & ~(cand[:, None] == out[:, :j]).any(axis=1)
                col[free] = cand[free]
                taken &= ~free
                q += 1
                q[q >= fleet.n_alive] -= fleet.n_alive
        out[:, j] = col
    return out
