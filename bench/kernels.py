"""The system's device kernels as the benchmark knows them: how to find
each in a trace, and the bytes it has to move at the least.

``ell_round``: one Jacobi round of the padded-ELL Bellman-Ford closure
(the ``ell-bf`` APSP forward), one lane at a time.  In a TPU trace it is a
``custom-call`` with ``custom_call_target="tpu_custom_call"`` whose first
operand is the int32 predecessor table (``s32[tiles,1,tile*d_max]``); its
output is the (N, S/128, 128) float32 distance carry.

Its compulsory traffic, for a fabric of ``n`` switches and table width
``d_max``: read the n x n float32 distances of the previous round once,
write the new ones once, and read the two tables (int32 index and float32
weight) once: ``8 n^2 + 8 n d_max`` bytes.  Padding the kernel adds is
not counted.  The VPU's peak is not published, so no operation bound is
used; the share is of the bandwidth bound alone, which can only
understate it.
"""
from __future__ import annotations

import re

_ELL_ROUND = re.compile(
    r"custom-call\(s32\[\d+,1,\d+\].*"
    r'custom_call_target="tpu_custom_call"')


def is_ell_round(op_name: str) -> bool:
    return bool(_ELL_ROUND.search(op_name))


def ell_round_bytes(n: int, d_max: int) -> int:
    return 8 * n * n + 8 * n * d_max
