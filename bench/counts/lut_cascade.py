"""Algorithmic work of the fused LUT cascade kernel
(``kernels/lut_cascade``).

Operations: one multiply-add per input bit-field of every lookup (forming
its address), plus one per lookup, two operations each.  Bytes: the
input codes read and the output codes written (int32), and every packed
table read once per call.  The shift-matmul and the mux tree are how the
kernel does it, not what the algorithm needs, and are not counted.
"""
from __future__ import annotations

# The kernel's device ops in a trace.  Pallas calls carry no name, so the
# trace names them by HLO instruction (``pallas_call.N``; ``jvp__.N`` and
# ``transpose_jvp___.N`` for the training kernel's two passes); each cell
# that reads this count runs no other compiled Pallas kernel, so the
# reader takes every ``tpu_custom_call`` op of its trace.
TRACE_PATTERN = r"^tpu_custom_call$"


def ops_per_sample(geom) -> int:
    return 2 * sum(o * (f + 1) for o, f in zip(geom.widths, geom.fan_ins))


def packed_words(geom, i: int) -> int:
    slots = 1 << ((32 // geom.beta).bit_length() - 1)
    return geom.table_size(i) // slots


def table_bytes(geom) -> int:
    return sum(4 * o * packed_words(geom, i)
               for i, o in enumerate(geom.widths))


def call_bytes(geom, slots: int) -> int:
    return table_bytes(geom) + 4 * slots * (geom.in_features
                                            + geom.widths[-1])
