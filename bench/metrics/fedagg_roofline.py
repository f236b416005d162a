"""The Pallas weighted-sum kernel's share of its roofline (kernels/fedagg).

The trace names each kernel call by its HLO instruction: a ``custom-call``
to ``tpu_custom_call`` with its result and operand shapes and layouts.
``fedagg`` is the only Pallas kernel on the training path; a kernel added
to that path needs a reader of its own and a name this one can tell apart.

Per call, the bytes the weighted sum must move are its operands read once
and its result written once, from those shapes.  A layout marked ``S(1)``
lives in the core's vector memory (XLA places small or hot buffers there),
which it reads at the VMEM bandwidth, the rest at the HBM bandwidth; its
operations are negligible beside those bytes.  The least time of a call is
the larger of the two memory times; the share is the calls' least time over
their device time in the trace.
"""
import math
import re

KERNEL = 'custom_call_target="tpu_custom_call"'
SHAPE = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)"
                   r"\[([0-9,]*)\](\{[^}]*\})?")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}


def call_bytes(name: str) -> tuple:
    """(HBM bytes, VMEM bytes) of one call's result and operands."""
    rest = name.partition(" = ")[2]
    result, _, operands = rest.partition("custom-call(")
    operands = operands.partition("), custom_call_target")[0]
    hbm = vmem = 0
    for part in (result, operands):
        for dtype, dims, layout in SHAPE.findall(part):
            n = BYTES[dtype] * math.prod(int(d) for d in dims.split(",") if d)
            if "S(1)" in layout:
                vmem += n
            else:
                hbm += n
    return hbm, vmem


def read(win):
    least = seconds = 0.0
    for name, t in win.ops.items():
        if KERNEL not in name:
            continue
        hbm, vmem = call_bytes(name)
        least += win.calls[name] * max(
            hbm / win.peaks["hbm_bytes_per_s"],
            vmem / win.peaks["vmem_read_bytes_per_s"])
        seconds += t
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
