"""HBM bytes a kernel call must move, from its operand and result shapes.

A TPU trace names each op event by its HLO instruction, e.g.
``%rowhash_pallas.7 = u32[131648,128]{...} custom-call(s32[5,131648,128]
{...} %x), custom_call_target="tpu_custom_call", ...``: the instruction
is named after the kernel's jitted function. The least bytes a call moves
are the data it reads once and the results it writes once. A kernel that
takes one array twice (``hash_neighbor_flags`` reads, beside each tile,
the row before it through a second view) is counted for the first only.
"""
from __future__ import annotations

import re
from typing import Optional

from kgbench.devtrace import hlo_parts

#: dtype bytes of HLO element types
_WIDTH = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
          "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
#: the Pallas kernels of the KG path: instruction name -> (kernel, how
#: many leading operands hold data the kernel must read)
KERNELS = {"rowhash_pallas": ("rowhash", 1),
           "hash_neighbor_flags_pallas": ("hash_neighbor_flags", 1),
           "radix_partition_pallas": ("radix_partition", 2)}


def shape_bytes(text: str) -> int:
    """Summed bytes of every array shape written in ``text``."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _WIDTH[dtype]
    return total


def _kernel(event):
    inst, _, opcode, _ = hlo_parts(event[0])
    if opcode != "custom-call":
        return None
    return KERNELS.get(re.sub(r"\.\d+$", "", inst))


def kernel_of(event) -> Optional[str]:
    """Which KG-path kernel an op event is, or ``None``."""
    k = _kernel(event)
    return k[0] if k else None


def _operands(text: str):
    depth, start = 0, 0
    for i, ch in enumerate(text):
        depth += ch in "({["
        depth -= ch in ")}]"
        if ch == "," and depth == 0:
            yield text[start:i]
            start = i + 1
    if text.strip():
        yield text[start:]


def hbm_bytes(event) -> Optional[int]:
    """The kernel call's data read once plus its results, in bytes."""
    k = _kernel(event)
    if k is None:
        return None
    _, shape, _, operands = hlo_parts(event[0])
    reads = list(_operands(operands))[:k[1]]
    return shape_bytes(shape) + sum(shape_bytes(r) for r in reads)
