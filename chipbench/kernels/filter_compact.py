"""Op and byte model of the Pallas segment-compaction kernel
(``repro/kernels/filter_compact.py``: each 128-row segment of a 32-bit
column compacted by a packed keep mask).

In the trace it is a ``tpu_custom_call`` named after the jitted function
that calls it; it is told apart by its shapes: two operands, a
``(rows/128, 128)`` 32-bit column and a ``(rows/32/128, 128)`` word mask,
and one result shaped as the column.  Per call it reads ``4 * rows`` bytes
of values and ``rows / 8`` bytes of mask and writes ``4 * rows`` bytes;
the shift network runs on the vector unit (no peak rate in the table), so
bytes only.
"""
import re

_DIMS = re.compile(r"\[(\d+),(\d+)\]")


def _rows(shape: str) -> int:
    m = _DIMS.search(shape)
    return int(m.group(1)) * int(m.group(2)) if m else -1


def matches(name: str) -> bool:
    return not name.startswith("_predicate_bitset_jit")


def fits(outs, ins) -> bool:
    return (len(outs) == 1 and len(ins) == 2 and outs[0][0] == ins[0][0]
            and _rows(ins[0][0]) == 32 * _rows(ins[1][0]))


def cost(outs, ins):
    return 0, sum(b for _, b in ins) + sum(b for _, b in outs)
