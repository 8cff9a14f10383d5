"""Plain numpy reference of zone-aware R-way placement, from its rule alone.

It imports nothing of the program, only the plain reference beside it
(``reference.py``: BinomialHash, the replacement-table divert, the salted
families and the re-salt), and adds the zones (DESIGN.md §13.5):

* slot ``s`` lies in zone ``s mod Z``, at local index ``s // Z`` of that
  zone's own replacement table, a ``reference.Fleet`` over the zone's slots
  that takes the same events as the whole fleet's table, in the same order;
* column 0 is the plain route;
* column ``j >= 1`` whose routed node lies in a zone an earlier column
  already uses, while more than ``j`` zones have an alive node, moves to
  the k-th zone in zone order among the alive zones no earlier column
  uses, ``k = mulhi(mix32(family ^ ZONE_SALT), alive zones - j)``, and to
  a node of that zone by the divert's two redirects over the zone's table:
  ``q = mulhi(h, zone size)`` with ``h = hash_pair(family, zone)``, and
  where ``q`` is a failed position, ``mulhi(mix32(h ^ q * golden), zone
  alive)``;
* then, as without zones, a column that repeats an earlier node is
  re-salted into the fleet's alive prefix and probed linearly.
"""
from __future__ import annotations

import numpy as np

import reference
from reference import GOLDEN, RESALT, U32, hash_pair, mix32

#: seeds the hash that picks a column's new zone
ZONE_SALT = 0x2C1B3C6D


def mulhi(a, b) -> np.ndarray:
    """floor(a * b / 2^32), elementwise over two u32 arrays."""
    prod = np.asarray(a, np.uint64) * np.asarray(b, np.uint64)
    return (prod >> np.uint64(32)).astype(U32)


class Table(reference.Fleet):
    """``reference.Fleet`` that also takes a recovery and a new last slot.
    Inside a zone the last slot is a node like any other, so ``fail`` takes
    it too; ``Zoned`` keeps the whole fleet's rule."""

    def _swap(self, p: int, q: int) -> None:
        a, b = int(self.slots[p]), int(self.slots[q])
        self.slots[p], self.slots[q] = b, a
        self.pos[a], self.pos[b] = q, p

    def fail(self, node: int) -> None:
        if self.pos[node] >= self.n_alive:
            raise ValueError(f"node {node} has already failed")
        self._swap(int(self.pos[node]), self.n_alive - 1)
        self.n_alive -= 1

    def recover(self, node: int) -> None:
        if self.pos[node] < self.n_alive:
            raise ValueError(f"node {node} is alive")
        self._swap(int(self.pos[node]), self.n_alive)
        self.n_alive += 1

    def grow(self) -> int:
        """A new node ``n_total`` joins the alive prefix."""
        t = self.n_total
        self.slots = np.append(self.slots, t)
        self.pos = np.append(self.pos, t)
        self._swap(t, self.n_alive)
        self.n_alive += 1
        return t


class Zoned:
    """The whole fleet's table and one table per zone, kept in step."""

    def __init__(self, n: int, zones: int):
        self.fleet = Table(n)
        self.zones = [Table(len(range(z, n, zones))) for z in range(zones)]

    def fail(self, node: int) -> None:
        if node == self.fleet.n_total - 1:
            raise ValueError("failing the last slot is a resize, not a failure")
        self.fleet.fail(node)
        self.zones[node % len(self.zones)].fail(node // len(self.zones))

    def recover(self, node: int) -> None:
        self.fleet.recover(node)
        self.zones[node % len(self.zones)].recover(node // len(self.zones))

    def grow(self) -> int:
        t = self.fleet.grow()
        self.zones[t % len(self.zones)].grow()
        return t

    def failed(self) -> np.ndarray:
        return self.fleet.failed()

    def alive_zones(self) -> int:
        return sum(1 for z in self.zones if z.n_alive)


def place(keys, zoned: Zoned, r: int, omega: int) -> np.ndarray:
    """Place each key on ``r`` distinct alive nodes spread over zones:
    (N, r) node ids."""
    keys = np.asarray(keys, U32).reshape(-1)
    Z = len(zoned.zones)
    size = np.array([z.n_total for z in zoned.zones], np.int64)
    alive = np.array([z.n_alive for z in zoned.zones], np.int64)
    n_up = int((alive > 0).sum())
    fleet = zoned.fleet
    out = np.empty((keys.size, r), np.int64)
    for j, salt in enumerate(reference.family_salts(r)):
        fam = mix32(keys ^ U32(salt))
        col = reference.route(fam, fleet, omega)
        if j and n_up > j:
            used = np.zeros((keys.size, Z), bool)
            used[np.arange(keys.size)[:, None], out[:, :j] % Z] = True
            move = used[np.arange(keys.size), col % Z]
            free = (alive > 0)[None, :] & ~used
            k = mulhi(mix32(fam ^ U32(ZONE_SALT)), np.full(keys.size, n_up - j))
            # the k-th free zone: the first whose running count of free
            # zones passes k
            target = np.argmax(free & (np.cumsum(free, axis=1) == k[:, None] + 1),
                               axis=1)
            h = hash_pair(fam, target.astype(U32))
            q = mulhi(h, size[target])
            deep = q >= alive[target]
            seed = h ^ (q * U32(GOLDEN))
            q = np.where(deep, mulhi(mix32(seed), alive[target]), q)
            local = np.zeros((Z, max(1, size.max())), np.int64)
            for z, table in enumerate(zoned.zones):
                local[z, : table.n_total] = table.slots
            col = np.where(move, local[target, q] * Z + target, col)
        if j:
            taken = (col[:, None] == out[:, :j]).any(axis=1)
            q = mulhi(mix32(fam ^ U32(RESALT)), np.full(keys.size, fleet.n_alive)
                      ).astype(np.int64)
            for _ in range(r):
                cand = fleet.slots[q]
                free = taken & ~(cand[:, None] == out[:, :j]).any(axis=1)
                col[free] = cand[free]
                taken &= ~free
                q += 1
                q[q >= fleet.n_alive] -= fleet.n_alive
        out[:, j] = col
    return out


def zones_spanned(held: np.ndarray, zones: int) -> np.ndarray:
    """(N,) distinct zones among each row's nodes."""
    z = np.asarray(held) % zones
    first = np.ones(z.shape, bool)
    for j in range(1, z.shape[1]):
        first[:, j] = (z[:, j][:, None] != z[:, :j]).all(axis=1)
    return first.sum(axis=1)
