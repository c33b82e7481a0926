"""Reduction of a profiler trace to the benchmark's device numbers.

`load` turns the newest `.xplane.pb` under a directory into a plain
record (lists of numbers and names, JSON-able, so a small recorded trace
can pin the reduction in a test):

    ops    [[module, instruction, start_ns, dur_ns], ...] per TPU device,
           from its "XLA Ops" line (else from events naming an hlo_op),
           the instruction's bare name (`instr_name`);
           an op's module is its `hlo_module` stat, else the event of the
           device's "XLA Modules" line that holds the op's start
    spans  [[name, start_ns, dur_ns], ...] host spans named "bench.*"

Everything else is arithmetic on that record: the traced window is the
span from the first traced `bench.init` to the last `bench.call` end;
busy time is the union of device-op intervals inside it, idle gaps are
its complement, each attributed to the host span it overlaps most.
Ops whose instructions are control flow (while, conditional, call) wrap
other ops and are left out of the union.
"""
from __future__ import annotations

import bisect
import glob
import os

CONTAINERS = ("while", "conditional", "call", "async-start", "async-done")


def load(trace_dir: str) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return {"ops": [], "spans": []}
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            named = [ln for ln in lines if ln.name == "XLA Ops"]
            modules = _Modules([e for ln in lines if ln.name == "XLA Modules"
                                for e in ln.events])
            dev = []
            for line in named or lines:
                for e in line.events:
                    st = dict(e.stats)
                    if named or "hlo_op" in st:
                        dev.append([str(st.get("hlo_module")
                                        or modules.at(e.start_ns)),
                                    instr_name(str(st.get("hlo_op", e.name))),
                                    float(e.start_ns), float(e.duration_ns)])
            ops.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    return {"ops": ops, "spans": spans}


def instr_name(name: str) -> str:
    """An op event's HLO instruction name.  On a TPU the event is named
    by the instruction's whole text, `%fusion.12 = f32[8]{0} fusion(...)`;
    elsewhere by the bare name, `fusion.12`."""
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


class _Modules:
    """Module events of one device, to name the module an op ran in."""

    def __init__(self, events):
        iv = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name) for e in events)
        self.starts = [a for a, _, _ in iv]
        self.iv = iv

    def at(self, t_ns: float) -> str:
        k = bisect.bisect_right(self.starts, t_ns) - 1
        if k >= 0 and t_ns < self.iv[k][1]:
            return self.iv[k][2]
        return ""


def window(rec: dict) -> tuple:
    """(start_ns, end_ns) of the traced whole calls, or None."""
    inits = [s for s in rec["spans"] if s[0] == "bench.init"]
    calls = [s for s in rec["spans"] if s[0] == "bench.call"]
    if not inits or not calls:
        return None
    return (min(s[1] for s in inits), max(s[1] + s[2] for s in calls))


def union(intervals) -> list:
    """Merged, sorted [start, end] intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def leaf_ops(dev_ops, opcode) -> list:
    """Ops that do work themselves: not control flow wrapping others.
    `opcode(op)` names the op's HLO opcode."""
    return [o for o in dev_ops if opcode(o) not in CONTAINERS]


def busy(dev_ops, win) -> list:
    return union(clip([[o[2], o[2] + o[3]] for o in dev_ops], *win))


def gaps(busy_iv, win) -> list:
    """Idle [start, end] intervals of the window."""
    out, t = [], win[0]
    for a, b in busy_iv:
        if a > t:
            out.append([t, a])
        t = max(t, b)
    if win[1] > t:
        out.append([t, win[1]])
    return out


def host_label(spans, a: float, b: float) -> str:
    """Name of the host span overlapping [a, b] most ("host" if none)."""
    best, label = 0.0, "host"
    for name, s, d in spans:
        ov = min(b, s + d) - max(a, s)
        if ov > best:
            best, label = ov, name
    return label
