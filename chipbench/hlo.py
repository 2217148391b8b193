"""The Pallas calls in a compiled step's HLO: for each ``tpu_custom_call``
instruction its name (the name its events carry in the device trace), the
kernel it runs (``jit(<kernel>)`` in its op name) and the shapes and
dtypes of its operands and result."""
from __future__ import annotations

import re
from typing import Any, Dict, List

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(")
_TYPE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}, \w+=")
_KERNEL = re.compile(r"jit\((\w+)\)/pallas_call")

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _types(text: str) -> List[Dict[str, Any]]:
    return [{"dtype": dt, "shape": tuple(int(d) for d in dims.split(",") if d)}
            for dt, dims in _TYPE.findall(text)]


def pallas_calls(hlo_text: str) -> List[Dict[str, Any]]:
    calls = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        k = _KERNEL.search(line)
        ops = _OPERANDS.search(line)
        if not (m and k and ops):
            continue
        calls.append({"name": m.group(1), "kernel": k.group(1),
                      "result": _types(m.group(2)),
                      "operands": _types(ops.group(1))})
    return calls


def nbytes(t: Dict[str, Any]) -> int:
    n = DTYPE_BYTES[t["dtype"]]
    for d in t["shape"]:
        n *= d
    return n
