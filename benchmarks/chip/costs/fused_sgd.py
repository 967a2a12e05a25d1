"""Operations and bytes of the fused momentum-SGD kernel
(``kernels/fused_sgd``), from its operands and results in the compiled
program.

The kernel reads the parameter, momentum and gradient streams and writes
the new parameter and momentum streams: every operand is read once and
every result written once, whatever their dtypes. Per element it does
v' = mu v + g and p' = p - lr v': 4 operations.
"""
from __future__ import annotations

# the kernel's Python entry, as the compiled kernel's body names it
MARK = b"sgd_momentum_flat"
FLOPS_PER_ELEMENT = 4


def cost(call: dict, shapes_bytes) -> tuple[float, float]:
    """(operations, bytes) of one call: ``call`` is one entry of
    ``trace.custom_calls``; ``shapes_bytes`` counts a shape list's
    elements and bytes."""
    elements, out_bytes = shapes_bytes(call["results"])
    _, in_bytes = shapes_bytes(call["operands"])
    return FLOPS_PER_ELEMENT * float(elements), float(in_bytes + out_bytes)
