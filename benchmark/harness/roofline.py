"""A kernel's share of its roofline, from the device trace.

least time of a call = max(operations / peak rate, bytes / HBM peak), with
operations and bytes from `kernels/<kernel>.py` and the call's shapes as
the trace states them; the share is the summed least time over the summed
time the calls took.  The bound that binds is printed."""

from __future__ import annotations

import re

from .common import say

_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|bf16|f16|f32|f64)\[([\d,]*)\]")


def parse_call(text: str) -> dict | None:
    """Operand and output shapes of an HLO custom-call instruction's text:
    `%name = (out, out) custom-call(operand, ...), ...`."""
    head, sep, tail = text.partition(" custom-call(")
    if not sep:
        return None

    def shapes(s: str) -> list:
        return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
                for m in _SHAPE.finditer(s)]

    depth, end = 1, 0
    for end, ch in enumerate(tail):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            break
    outputs, operands = shapes(head), shapes(tail[:end])
    if not outputs or not operands:
        return None
    return {"operands": operands, "outputs": outputs}


DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}


def call_bytes(call: dict) -> int:
    """Bytes a call moves once: every operand in, every output out."""
    total = 0
    for dtype, shape in call["operands"] + call["outputs"]:
        n = DTYPE_BYTES[dtype]
        for d in shape:
            n *= d
        total += n
    return total


def share(inp, kernel_name: str):
    kernel = inp.kernel(kernel_name)
    events = inp.trace.matching(kernel.MATCH)
    if not events or not inp.peaks:
        return None
    took = least = ops_bound = 0.0
    for e in events:
        call = parse_call(e.detail)
        if call is None:
            say(f"metric {kernel_name}_roofline: the trace names the kernel but "
                "not the shapes of its calls")
            return None
        ops, nbytes = kernel.work(call)
        t_ops = ops / inp.peaks["flops_per_s"]
        t_bytes = nbytes / inp.peaks["hbm_bytes_per_s"]
        least += max(t_ops, t_bytes)
        ops_bound += t_ops > t_bytes
        took += e.dur_s
    say(f"roofline {kernel_name}: {len(events)} calls, {took:.4f} s on the device, "
        f"least time {least:.4f} s, bound by "
        f"{'operations' if ops_bound > len(events) / 2 else 'HBM bytes'}")
    return 100.0 * least / took
