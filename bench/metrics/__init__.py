"""Per-layer metric readers: `bench/metrics/<metric>.py`, one per metric.

Each reader module defines `read(ctx) -> float | None`.  A reader that
finds nothing to read returns None and the metric is left out of the
result line; a share is never reported as 0 for want of events.
`Context` holds what the readers read: the window's host-clock spans,
the compile time, and the reduced device trace with every op looked up
in the compiled programs' HLO.

A device op whose (module, instruction) the HLO tables do not hold
cannot be placed in a layer, and may be control flow that wraps the
rest.  Where such ops take more than `MAX_UNMATCHED` of the busy time,
the trace and the programs do not describe the same work: every reader
of the trace then finds nothing, and `unmatched_share` says why.
"""
from __future__ import annotations

import importlib

from bench import hlo, trace

MAX_UNMATCHED = 0.01      # of device busy time


def _module(name: str) -> str:
    return name.split("(", 1)[0]


class Context:
    """spans: [(init start, init end, call end)] host clock per window
    call; rec: an already loaded trace record instead of `trace_dir`."""

    def __init__(self, *, spans, compile_s, trace_dir, hlo_texts, rec=None):
        self.spans = spans
        self.compile_s = compile_s
        self.table, by_name = {}, {}
        for text in hlo_texts:
            module, instrs = hlo.parse(text)
            for name, ins in instrs.items():
                self.table[(module, name)] = ins
                by_name.setdefault(name, []).append(ins)
        # an op whose module the trace does not name: its instruction
        # name, where only one program has it
        self.table.update({("", n): v[0] for n, v in by_name.items()
                           if len(v) == 1})
        self.rec = trace.load(trace_dir) if rec is None else rec
        self.win = trace.window(self.rec)
        self.devices = []          # per device: (leaf ops in window, busy)
        if self.win is not None:
            for dev in self.rec["ops"]:
                ops = [[_module(o[0])] + o[1:] for o in dev]
                ops = [o for o in trace.leaf_ops(
                           ops, lambda op: self.instr(op).opcode)
                       if o[2] < self.win[1] and o[2] + o[3] > self.win[0]]
                self.devices.append((ops, trace.busy(ops, self.win)))

    @property
    def window_s(self):
        return None if self.win is None else (self.win[1] - self.win[0]) / 1e9

    @property
    def unmatched_share(self):
        """Device time of ops not found in the HLO over busy time, or
        None without a traced window."""
        busy = sum(b - a for _, iv in self.devices for a, b in iv)
        if not busy:
            return None
        return sum(o[3] for ops, _ in self.devices for o in ops
                   if self._lookup(o) is None) / busy

    @property
    def busy_s(self):
        """Device-busy seconds in the traced window, mean over devices;
        None where the trace's ops are not the programs' (module doc)."""
        share = self.unmatched_share
        if share is None or share > MAX_UNMATCHED:
            return None
        return sum(sum(b - a for a, b in busy) for _, busy in self.devices) \
            / len(self.devices) / 1e9

    def _lookup(self, op):
        return self.table.get((op[0], op[1])) or self.table.get(("", op[1]))

    def instr(self, op) -> hlo.Instr:
        return self._lookup(op) or hlo.Instr("", "", "")

    def op_seconds(self, pred) -> float:
        """Device seconds, summed over devices, of ops whose instruction
        satisfies pred."""
        return sum(o[3] for ops, _ in self.devices for o in ops
                   if pred(self.instr(o))) / 1e9

    def share_of_busy(self, pred):
        busy = self.busy_s
        if not busy:
            return None
        return 100.0 * self.op_seconds(pred) / (busy * len(self.devices))

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time, by instruction, and the
        longest idle gaps, by the host span they fall in."""
        by_op = {}
        for ops, _ in self.devices:
            for o in ops:
                scope = self.instr(o).op_name.replace(
                    "/vmap()", "").split("/while/body/", 1)[-1]
                label = f"{o[1]} {scope}"[:120]
                by_op[label] = by_op.get(label, 0.0) + o[3] / 1e9
        idle = []
        for _, busy in self.devices:
            for a, b in trace.gaps(busy, self.win):
                idle.append([trace.host_label(self.rec["spans"], a, b),
                             (b - a) / 1e9])
        return {"device_ops": sorted(by_op.items(), key=lambda x: -x[1])[:top],
                "idle_gaps": sorted(idle, key=lambda x: -x[1])[:top]}


def read_all(per_layer: list, ctx: Context) -> dict:
    out = {}
    for m in per_layer:
        reader = importlib.import_module(f"bench.metrics.{m['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
