"""Drive `repro.workloads.worksteal`: one unvmapped work-steal round per call.

`inputs` is the workload's own `build`: the collab_like graph on the
host, the chunk plan, and the device enqueue.  `call` runs
`harness.runner(engine)` on the result.  The event count of a call is
the number of task chunks enqueued, fixed by the configuration.
"""
from __future__ import annotations

import numpy as np

from bench.reference import worksteal as reference

COUNTERS = ("cycles", "l2_accesses", "wb_blocks", "inv_full",
            "inv_per_cache", "probes", "promotions", "local_syncs",
            "remote_syncs", "global_syncs", "l1_hits", "l1_misses",
            "steals", "recoveries")


class Driver:
    def __init__(self, config: dict, traffic: dict, proto=None):
        from repro.core import tables
        from repro.core.costmodel import CostParams
        from repro.workloads import harness, worksteal

        if int(traffic["replicas"]) != 1:
            raise ValueError("worksteal runs one unvmapped replica per call")
        self.mod = worksteal
        self.config = config
        self.scenario = traffic["scenario"]
        self.proto = proto
        self.kw = dict(chunk_cap=config["chunk_cap"],
                       n_chunks_max=config["n_chunks_max"],
                       fifo_cap=config["fifo_cap"],
                       cold_factor=config["cold_factor"],
                       lr_tbl=tables.TableGeometry(**config["lr_tbl"]),
                       pa_tbl=tables.TableGeometry(**config["pa_tbl"]),
                       params=CostParams(**config["cost"]))
        self.ws = worksteal.WSConfig(n_wgs=config["n_wgs"], **self.kw)
        # the workload's build() fixes the graph: these keys only state it
        if (config["graph_nodes"], config["graph_m"], config["iterations"]) \
                != (self.ws.n_chunks_max * self.ws.chunk_cap // 2, 3, 1):
            raise ValueError("graph_nodes/graph_m/iterations disagree with "
                             "worksteal.build")
        self.replicas = 1
        self.events_per_call = -(-config["graph_nodes"] // self.ws.chunk_cap)
        self.p = harness.resolve_proto(self.scenario, proto)
        self._run = harness.runner(traffic["engine"])

    def compile(self) -> dict:
        """Compile the enqueue and the engine for the shapes `build` makes."""
        import jax
        import jax.numpy as jnp

        from repro.core import protocol as P

        ws, n, m = self.ws, self.ws.n_wgs, self.ws.n_chunks_max
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
        store = jax.eval_shape(lambda: P.make_store(ws.proto_cfg()))
        enqueue = self.mod._enqueue_jit.lower(
            ws, self.p.acquire_loc_b, self.p.release_loc_b, store,
            i32(m), i32(m), jax.ShapeDtypeStruct((m,), jnp.bool_),
            i32(n)).compile()
        state = self.mod.SimState(store=store, qsize=i32(n),
                                  processed=i32(m), last_inv=f32(n),
                                  rounds=i32(), rem=f32(n))
        wl = self.mod.build_workload(ws, self.p, self._steal())
        engine = self._run.lower(wl, state, i32(m), f32(m)).compile()
        return {"enqueue": enqueue, "engine": engine}

    def _steal(self) -> bool:
        return self.mod.SCENARIOS[self.scenario][1]

    def inputs(self, seeds: np.ndarray):
        return self.mod.build(self.scenario, self.ws.n_wgs,
                              seed=int(seeds[0]), proto=self.proto,
                              **self.kw)

    def call(self, bench):
        return self._run(bench.wl, bench.state, *bench.ops)

    def fetch(self, out) -> dict:
        c = out.store.counters
        leaves = {k: getattr(c, k) for k in COUNTERS}
        leaves.update(processed=out.processed, qsize=out.qsize,
                      last_inv=out.last_inv, rem=out.rem, rounds=out.rounds,
                      l2=out.store.l2)
        for x in leaves.values():
            x.copy_to_host_async()
        return leaves

    def replica(self, host: dict, r: int) -> dict:
        return {k: np.asarray(v) for k, v in host.items()}

    def served(self, host: dict) -> tuple:
        """(chunks not processed exactly once, 0): each enqueued chunk
        must be taken once, and no slot past them ever."""
        proc = np.asarray(host["processed"])
        nc = self.events_per_call
        return (int(np.abs(proc[:nc] - 1).sum() + proc[nc:].sum()), 0)

    def reference(self, seeds: np.ndarray) -> list:
        c = self.config
        return [reference.simulate(
            n_wgs=c["n_wgs"], chunk_cap=c["chunk_cap"],
            n_chunks_max=c["n_chunks_max"], graph_nodes=c["graph_nodes"],
            graph_m=c["graph_m"], graph_seed=1 + int(seeds[0]),
            fifo_cap=c["fifo_cap"],
            lr_geom=(c["lr_tbl"]["sets"], c["lr_tbl"]["ways"]),
            pa_geom=(c["pa_tbl"]["sets"], c["pa_tbl"]["ways"]),
            cost=c["cost"], cold_factor=c["cold_factor"],
            protocol=self.scenario)]
