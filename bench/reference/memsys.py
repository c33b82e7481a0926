"""Plain reference of the simulated memory system, one operation at a time.

An independent, deliberately straightforward model of the sRSP / RSP
memory system that the simulator under test implements (PAPER.md §2-4):
N private write-combining L1 caches over a shared L2, each L1 with a
16-entry dirty-block FIFO (sFIFO), a local-release table (LR-TBL:
address -> FIFO position of the last local release) and a
promoted-acquire table (PA-TBL), both set-associative with LRU ways.
Every operation charges cycles to per-cache clocks by the cost rules of
the simulator's cost model (Table 1 latencies), in float32 and in the
same order, so that the statistics of a run come out bit for bit.

It imports nothing of the program.  One operation acts for one cache;
the simulator's batched and fused engines must reproduce what this
model does when agents act one at a time in the serial order (smallest
clock first, ties to the lowest index).
"""
from __future__ import annotations

import numpy as np

F32 = np.float32
NO_SEQ = 2 ** 30        # larger than any FIFO sequence number
DRAIN_ALL = 2 ** 30


class Table:
    """Set-associative CAM with LRU ways.  `ptrs` is kept for the LR-TBL
    (FIFO position of the release); the PA-TBL ignores it.  A reset
    clears the addresses only: ages and the age counter run on."""

    def __init__(self, sets: int, ways: int):
        self.sets, self.ways = sets, ways
        self.addr = [[-1] * ways for _ in range(sets)]
        self.ptr = [[0] * ways for _ in range(sets)]
        self.age = [[0] * ways for _ in range(sets)]
        self.next_age = 0

    def _set(self, a: int) -> int:
        return (a >> 4) % self.sets

    def lookup(self, a: int) -> int:
        """Recorded pointer of `a`, or -1."""
        s = self._set(a)
        for w in range(self.ways):
            if self.addr[s][w] == a:
                return self.ptr[s][w]
        return -1

    def contains(self, a: int) -> bool:
        return a in self.addr[self._set(a)]

    def insert(self, a: int, ptr: int = 0) -> tuple:
        """Insert or refresh `a`; returns the (addr, ptr) it evicted from a
        full set, else (-1, -1).  Way choice: the hit, else the first free
        way, else the least recently touched."""
        s = self._set(a)
        row = self.addr[s]
        evicted = (-1, -1)
        if a in row:
            w = row.index(a)
        elif -1 in row:
            w = row.index(-1)
        else:
            w = min(range(self.ways), key=lambda i: self.age[s][i])
            evicted = (row[w], self.ptr[s][w])
        row[w] = a
        self.ptr[s][w] = ptr
        self.age[s][w] = self.next_age
        self.next_age += 1
        return evicted

    def remove(self, a: int) -> None:
        row = self.addr[self._set(a)]
        for w in range(self.ways):
            if row[w] == a:
                row[w] = -1

    def reset(self) -> None:
        self.addr = [[-1] * self.ways for _ in range(self.sets)]


class Fifo:
    """Dirty-block FIFO: a set of blocks tagged with push sequence numbers.
    A write to a block already queued keeps its place; a release moves it
    to the tail.  A full FIFO evicts its oldest block."""

    def __init__(self, cap: int):
        self.blk = [-1] * cap
        self.seq = [0] * cap
        self.next_seq = 0

    def push(self, b: int, to_tail: bool) -> tuple:
        """-> (evicted block or -1, sequence number of b's entry)."""
        if b in self.blk:
            slot, evicted = self.blk.index(b), -1
            if not to_tail:
                return -1, self.seq[slot]
        elif -1 in self.blk:
            slot, evicted = self.blk.index(-1), -1
        else:
            slot = min(range(len(self.blk)), key=lambda i: self.seq[i])
            evicted = self.blk[slot]
        self.blk[slot] = b
        self.seq[slot] = pos = self.next_seq
        self.next_seq += 1
        return evicted, pos

    def drain(self, upto: int) -> list:
        """Remove and return, oldest first, every block with seq <= upto."""
        out = sorted((self.seq[i], self.blk[i]) for i in range(len(self.blk))
                     if self.blk[i] >= 0 and self.seq[i] <= upto)
        for i in range(len(self.blk)):
            if self.blk[i] >= 0 and self.seq[i] <= upto:
                self.blk[i] = -1
        return [b for _, b in out]


class MemSys:
    """L2 + N L1s + per-cache FIFO / LR-TBL / PA-TBL + counters."""

    COUNTS = ("l2_accesses", "wb_blocks", "inv_full", "probes",
              "promotions", "local_syncs", "remote_syncs", "global_syncs",
              "l1_hits", "l1_misses", "steals", "recoveries")

    def __init__(self, n_caches: int, n_words: int, *, fifo_cap: int,
                 lr_geom: tuple, pa_geom: tuple, cost: dict,
                 block_words: int = 16):
        self.n, self.W = n_caches, block_words
        self.nb = -(-n_words // block_words)
        self.l2 = np.zeros((self.nb, self.W), np.int32)
        self.l1 = np.zeros((n_caches, self.nb, self.W), np.int32)
        self.valid = np.zeros((n_caches, self.nb, self.W), bool)
        self.dirty = np.zeros((n_caches, self.nb, self.W), bool)
        self.fifo = [Fifo(fifo_cap) for _ in range(n_caches)]
        self.lr = [Table(*lr_geom) for _ in range(n_caches)]
        self.pa = [Table(*pa_geom) for _ in range(n_caches)]
        self.cyc = np.zeros(n_caches, F32)
        self.inv_per_cache = np.zeros(n_caches, F32)
        self.count = dict.fromkeys(self.COUNTS, 0)
        self.p = {k: F32(v) for k, v in cost.items()}

    # ---- cost accounting ----
    def charge(self, i: int, cycles) -> None:
        self.cyc[i] = self.cyc[i] + F32(cycles)

    def stats(self) -> dict:
        out = {k: F32(v) for k, v in self.count.items()}
        out.update(cycles=self.cyc.copy(),
                   inv_per_cache=self.inv_per_cache.copy(),
                   l2=self.l2.copy())
        return out

    # ---- writeback machinery ----
    def _write_back(self, i: int, b: int) -> bool:
        """Dirty words of cache i's block b go to L2; True if any moved."""
        d = self.dirty[i, b]
        if not d.any():
            return False
        self.l2[b, d] = self.l1[i, b, d]
        self.dirty[i, b] = False
        return True

    def drain(self, pos: dict) -> dict:
        """Selective flush of several caches at once: cache i drains its
        FIFO up to seq pos[i] and every charged cache pays l2_lat plus
        wb_per_block per block written back, whether or not anything
        drained.  Caches write back in ascending order, so a higher cache
        wins a word two of them hold dirty.  -> blocks written back per
        cache."""
        n_wb = {}
        for i in sorted(pos):
            n_wb[i] = sum(self._write_back(i, b)
                          for b in self.fifo[i].drain(pos[i]))
        for i in sorted(pos):
            self.count["l2_accesses"] += n_wb[i]
            self.count["wb_blocks"] += n_wb[i]
            self.charge(i, self.p["l2_lat"]
                        + F32(n_wb[i]) * self.p["wb_per_block"])
        return n_wb

    def invalidate(self, caches) -> None:
        """Whole-cache invalidate: full flush (charged), drop every valid
        word, clear the LR-TBL and PA-TBL, one flash cycle."""
        caches = list(caches)
        self.drain({i: DRAIN_ALL for i in caches})
        for i in caches:
            self.valid[i] = False
            self.lr[i].reset()
            self.pa[i].reset()
            self.charge(i, self.p["inv_flash"])
            self.count["inv_full"] += 1
            self.inv_per_cache[i] = self.inv_per_cache[i] + F32(1.0)

    # ---- plain loads and stores through the L1 ----
    def load(self, i: int, a: int) -> int:
        b, o = divmod(a, self.W)
        if self.valid[i, b, o]:
            self.count["l1_hits"] += 1
            self.charge(i, self.p["l1_lat"])
            return int(self.l1[i, b, o])
        v = int(self.l2[b, o])
        self.l1[i, b, o] = v
        self.valid[i, b, o] = True
        self.count["l1_misses"] += 1
        self.count["l2_accesses"] += 1
        self.charge(i, self.p["l1_lat"] + self.p["l2_lat"])
        return v

    def store(self, i: int, a: int, v: int, release: bool = False) -> int:
        """Write-combining, no-allocate store; a full FIFO writes its oldest
        block back.  -> the FIFO sequence number of the block."""
        b, o = divmod(a, self.W)
        self.l1[i, b, o] = v
        self.valid[i, b, o] = True
        self.dirty[i, b, o] = True
        evicted, pos = self.fifo[i].push(b, release)
        moved = evicted >= 0 and self._write_back(i, evicted)
        self.count["l2_accesses"] += moved
        self.count["wb_blocks"] += moved
        self.charge(i, self.p["l1_lat"] + F32(moved) * self.p["wb_per_block"])
        return pos

    # ---- atomics ----
    def cas_l1(self, i: int, a: int, expect: int, new: int) -> int:
        cur = self.load(i, a)
        if cur == expect:
            self.store(i, a, new)
        return cur

    def atomic_l2(self, i: int, a: int, new: int, expect=None) -> int:
        """Atomic at L2 (CAS when `expect` is given, else a store).  The
        issuer's L1 copy of the word stops being valid or dirty."""
        b, o = divmod(a, self.W)
        cur = int(self.l2[b, o])
        if expect is None or cur == expect:
            self.l2[b, o] = new
        self.valid[i, b, o] = False
        self.dirty[i, b, o] = False
        self.charge(i, self.p["l2_lat"])
        self.count["l2_accesses"] += 1
        return cur

    # ---- local (work-group) scope, shared by sRSP and RSP ----
    def local_acquire(self, i: int, a: int, expect: int, new: int) -> int:
        """A PA-TBL hit promotes: invalidate, then CAS at L2.  Otherwise
        the CAS runs in the L1."""
        if self.pa[i].contains(a):
            self.invalidate([i])
            old = self.atomic_l2(i, a, new, expect)
            self.count["promotions"] += 1
        else:
            old = self.cas_l1(i, a, expect, new)
        self.charge(i, self.p["tbl_lat"])
        self.count["local_syncs"] += 1
        return old

    def local_release(self, i: int, a: int, v: int) -> None:
        """Store to the FIFO tail and record its position in the LR-TBL; a
        record evicted from a full set drains the FIFO up to its position.
        The drain is charged even when no record was evicted."""
        pos = self.store(i, a, v, release=True)
        ev_addr, ev_ptr = self.lr[i].insert(a, pos)
        self.drain({i: ev_ptr if ev_addr >= 0 else -1})
        self.charge(i, self.p["tbl_lat"])
        self.count["local_syncs"] += 1

    # ---- remote scope: sRSP (selective promotion) ----
    def srsp_remote_acquire(self, i: int, a: int, expect: int,
                            new: int) -> int:
        own = self.lr[i].lookup(a)
        if own >= 0:
            # the local sharer is this cache: order its releases, CAS at L2
            self.drain({i: own})
            self.lr[i].remove(a)
        else:
            # selective-flush probe: only caches whose LR-TBL records `a`
            # drain (up to the recorded position) and start promoting it
            ptrs = {j: self.lr[j].lookup(a) for j in range(self.n) if j != i}
            sharers = {j: p for j, p in ptrs.items() if p >= 0}
            n_wb = self.drain(sharers)
            for j in sharers:
                self.lr[j].remove(a)
                self.pa[j].insert(a)
            wait = F32(sum(self.p["l2_lat"] + F32(n_wb[j])
                           * self.p["wb_per_block"] for j in sorted(sharers))
                       + F32(0.0)) + F32(1.0)
            for j in ptrs:
                if j not in sharers:
                    self.charge(j, self.p["tbl_lat"])
            self.charge(i, self.p["probe_lat"] + self.p["l2_lat"] + wait)
            self.count["probes"] += self.n - 1
            self.invalidate([i])
        old = self.atomic_l2(i, a, new, expect)
        self.count["remote_syncs"] += 1
        return old

    def srsp_remote_release(self, i: int, a: int, v: int) -> None:
        """Flush own cache, store at L2, every PA-TBL records `a`."""
        self.drain({i: DRAIN_ALL})
        self.atomic_l2(i, a, v)
        for j in range(self.n):
            self.pa[j].insert(a)
            if j != i:
                self.charge(j, self.p["tbl_lat"])
        self.charge(i, self.p["probe_lat"] + F32(1.0))
        self.count["probes"] += self.n
        self.count["remote_syncs"] += 1

    # ---- remote scope: original RSP (flush and invalidate everyone) ----
    def rsp_remote_acquire(self, i: int, a: int, expect: int,
                           new: int) -> int:
        n_wb = self.drain({j: DRAIN_ALL for j in range(self.n)})
        wait = F32(0.0)
        for j in range(self.n):
            wait = wait + (self.p["l2_lat"]
                           + F32(n_wb[j]) * self.p["wb_per_block"])
        self.charge(i, self.p["probe_lat"] + wait)
        self.count["probes"] += self.n - 1
        self.invalidate([i])
        old = self.atomic_l2(i, a, new, expect)
        self.count["remote_syncs"] += 1
        return old

    def rsp_remote_release(self, i: int, a: int, v: int) -> None:
        self.drain({i: DRAIN_ALL})
        self.atomic_l2(i, a, v)
        self.invalidate(range(self.n))
        self.charge(i, self.p["probe_lat"] + F32(self.n) * self.p["l2_lat"])
        self.count["probes"] += self.n
        self.count["remote_syncs"] += 1

    def remote_acquire(self, protocol: str, i, a, expect, new) -> int:
        return {"srsp": self.srsp_remote_acquire,
                "rsp": self.rsp_remote_acquire}[protocol](i, a, expect, new)

    def remote_release(self, protocol: str, i, a, v) -> None:
        {"srsp": self.srsp_remote_release,
         "rsp": self.rsp_remote_release}[protocol](i, a, v)
