"""Plain reference of one work-steal round (PAPER.md §5.1), one turn at a time.

The graph's nodes are cut into chunks of `chunk_cap` in node order; the
first half of the chunks belong to agent 0 and the rest go round-robin.
Every agent enqueues its chunks into its own queue in one local-scope
critical section.  Then the agent with the smallest clock acts next
(ties to the lowest index): with work in its own queue it pops the tail
under a local-scope lock; with an empty queue it steals the head of the
fullest other queue under a remote-scope lock.  The agent that takes a
chunk pays task_base + per_edge * edges cycles, plus a refill penalty if
its L1 was invalidated since its last chunk, and writes the chunk id
into the chunk's output words.  Queue q's lock, head and tail words sit
at q * qstride + 0, 1, 2, its task words from q * qstride + 16 on.
"""
from __future__ import annotations

import numpy as np

from bench.reference.graphs import collab_degrees
from bench.reference.memsys import F32, MemSys

QMETA = 16


def _round16(x: int) -> int:
    return (x + 15) // 16 * 16


def chunk_plan(*, n_wgs: int, chunk_cap: int, n_chunks_max: int,
               degrees: np.ndarray) -> dict:
    nf = len(degrees)
    n_chunks = min(-(-nf // chunk_cap), n_chunks_max)
    owner = np.zeros(n_chunks_max, np.int64)
    count = np.zeros(n_chunks_max, np.int64)
    edges = np.zeros(n_chunks_max, np.float32)
    slot = np.zeros(n_chunks_max, np.int64)
    n_enq = np.zeros(n_wgs, np.int64)
    for c in range(n_chunks):
        sel = degrees[c * chunk_cap:(c + 1) * chunk_cap]
        owner[c] = 0 if c < n_chunks // 2 else c % n_wgs
        count[c] = len(sel)
        edges[c] = np.float32(int(sel.sum()))
        slot[c] = n_enq[owner[c]]
        n_enq[owner[c]] += 1
    return dict(n_chunks=n_chunks, owner=owner, count=count, edges=edges,
                slot=slot, n_enq=n_enq)


def simulate(*, n_wgs: int, chunk_cap: int, n_chunks_max: int,
             graph_nodes: int, graph_m: int, graph_seed: int,
             fifo_cap: int, lr_geom: tuple, pa_geom: tuple, cost: dict,
             cold_factor: float, protocol: str) -> dict:
    """Statistics of one round on the graph collab_like(graph_nodes,
    graph_m, graph_seed)."""
    plan = chunk_plan(n_wgs=n_wgs, chunk_cap=chunk_cap,
                      n_chunks_max=n_chunks_max,
                      degrees=collab_degrees(graph_nodes, graph_m,
                                             graph_seed))
    qcap = n_chunks_max
    qstride = _round16(QMETA + qcap)
    data_base = n_wgs * qstride
    n_words = _round16(data_base + n_chunks_max * chunk_cap)
    ms = MemSys(n_wgs, n_words, fifo_cap=fifo_cap, lr_geom=lr_geom,
                pa_geom=pa_geom, cost=cost)
    p = ms.p
    nc = plan["n_chunks"]
    owner, slot = plan["owner"], plan["slot"]
    count, edges, n_enq = plan["count"], plan["edges"], plan["n_enq"]

    # enqueue: one critical section per agent, its own queue and cache
    rem = np.zeros(n_wgs, np.float32)
    for c in range(nc):
        rem[owner[c]] = rem[owner[c]] + (p["task_base"]
                                         + p["per_edge"] * edges[c])
    for q in range(n_wgs):
        lock = q * qstride
        ms.local_acquire(q, lock, 0, 1)
        for c in np.nonzero(owner[:nc] == q)[0]:
            b, o = divmod(lock + QMETA + int(slot[c]), 16)
            ms.l1[q, b, o] = c + 1
            ms.valid[q, b, o] = ms.dirty[q, b, o] = True
        first = (lock + QMETA) // 16
        for t in range(-(-int(n_enq[q]) // 16)):
            evicted, _ = ms.fifo[q].push(first + t, False)
            if evicted >= 0 and ms._write_back(q, evicted):
                ms.count["l2_accesses"] += 1
                ms.count["wb_blocks"] += 1
        ms.store(q, lock + 1, 0)
        ms.store(q, lock + 2, int(n_enq[q]))
        ms.local_release(q, lock, 0)
        ms.charge(q, F32(n_enq[q]) * p["l1_lat"])

    qsize = n_enq.astype(np.int64)
    processed = np.zeros(n_chunks_max, np.int32)
    last_inv = np.zeros(n_wgs, np.float32)
    rounds = 0
    max_rounds = 2 * n_chunks_max + 4 * n_wgs

    def process(i: int, chunk: int, rem_of: int) -> None:
        if not 0 <= chunk < n_chunks_max:
            return
        processed[chunk] += 1
        base = p["task_base"] + p["per_edge"] * edges[chunk]
        rem[rem_of] = max(rem[rem_of] - base, F32(0.0))
        inv_now = ms.inv_per_cache[i]
        work = base
        if inv_now > last_inv[i]:
            touched = F32(count[chunk]) + edges[chunk] / F32(4.0)
            work = base + F32(cold_factor) * touched * (p["l2_lat"] / F32(4))
        ms.charge(i, work)
        last_inv[i] = inv_now
        for k in range(chunk_cap // 16 + 1):
            if k * 16 < count[chunk]:
                a = data_base + chunk * chunk_cap + k * 16
                ms.store(i, min(a, n_words - 1), chunk)

    while qsize.sum() > 0 and rounds < max_rounds:
        i = int(np.argmin(ms.cyc))
        if qsize[i] > 0:                          # pop own tail
            lock = i * qstride
            got = ms.local_acquire(i, lock, 0, 1) == 0
            tail = ms.load(i, lock + 2)
            head = ms.load(i, lock + 1)
            has = got and head < tail
            task = ms.load(i, lock + QMETA + min(max(tail - 1, 0), qcap - 1))
            if has:
                ms.store(i, lock + 2, tail - 1)
            if got:
                ms.local_release(i, lock, 0)
            chunk = task - 1 if has else -1
            qsize[i] = max(qsize[i] - has, 0)
            process(i, chunk, i)
        else:                                     # steal another's head
            others = qsize.copy()
            others[i] = 0
            victim = int(np.argmax(others))
            chunk = -1
            if others[victim] > 0:
                lock = victim * qstride
                got = ms.remote_acquire(protocol, i, lock, 0, 1) == 0
                head = ms.load(i, lock + 1)
                tail = ms.load(i, lock + 2)
                has = got and head < tail
                task = ms.load(i, lock + QMETA + min(max(head, 0), qcap - 1))
                if has:
                    ms.store(i, lock + 1, head + 1)
                if got:
                    ms.remote_release(protocol, i, lock, 0)
                ms.count["steals"] += has
                chunk = task - 1 if has else -1
                if chunk >= 0:
                    qsize[victim] -= 1
            process(i, chunk, victim)
        rounds += 1

    out = ms.stats()
    out.update(processed=processed, qsize=qsize.astype(np.int32),
               last_inv=last_inv, rem=rem, rounds=np.int32(rounds))
    return out
