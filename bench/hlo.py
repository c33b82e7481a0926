"""What the benchmark reads from a compiled program's HLO text.

For every instruction: its opcode, the name-scope path of its metadata
(`op_name`), and for a Pallas kernel call (`tpu_custom_call`) its
kernel.  Device events in a profile carry the instruction's name, so
this is how the trace reduction learns which layer an event belongs to.
"""
from __future__ import annotations

import re
from typing import NamedTuple

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


class Instr(NamedTuple):
    opcode: str      # fusion, while, custom-call, ...
    op_name: str     # name-scope path from the metadata ("" when absent)
    kernel: str      # Pallas kernel name for a tpu_custom_call, else ""


def _result_type(rhs: str) -> str:
    """The result type at the head of an instruction's right-hand side:
    one shape, or a parenthesised tuple of them."""
    if not rhs.startswith("("):
        return rhs.split(" ", 1)[0]
    depth = 0
    for k, ch in enumerate(rhs):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            return rhs[:k + 1]
    return rhs


def kernel_of(instr_name: str) -> str:
    """`drain_writeback_pallas.98` -> `drain_writeback`."""
    base = instr_name.split(".", 1)[0]
    return base[:-len("_pallas")] if base.endswith("_pallas") else base


def parse(text: str) -> tuple:
    """-> (module name, {instruction name: Instr})."""
    module, table = "", {}
    for line in text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        op = _OP_NAME.search(rhs)
        result = _result_type(rhs)
        opcode = rhs[len(result):].strip().split("(", 1)[0]
        kernel = (kernel_of(name)
                  if 'custom_call_target="tpu_custom_call"' in rhs else "")
        table[name] = Instr(opcode, op.group(1) if op else "", kernel)
    return module, table
