"""Required work of the chunk product and its share of the chip's roofline.

The work is what the algorithm needs, counted from shapes and never from
what the program happens to move:

* bytes: the chunk's rows of A at the dtype the device holds them, the
  operand x unpadded (``d × B``), and the ``rows × B`` output;
* FLOPs: ``2 · rows · d · B``.

A program that pads x to 128 lanes moves more than this, and a program
that stops padding shows as a higher share.  Peaks come from
``bench/peaks.json``, keyed by ``device_kind``; a device that is not in
the table is an error.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, Tuple

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS}; "
                       f"known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}


def chunk_bytes(rows: int, d: int, width: int, a_itemsize: int = 4,
                x_itemsize: int = 4, out_itemsize: int = 4) -> int:
    """Bytes one chunk product must move: A rows, x unpadded, output."""
    return (rows * d * a_itemsize + d * width * x_itemsize
            + rows * width * out_itemsize)


def chunk_flops(rows: int, d: int, width: int) -> int:
    return 2 * rows * d * width


def roofline_share(total_bytes: float, total_flops: float, seconds: float,
                   peak: Dict[str, float]) -> Tuple[float, str]:
    """(least time the chip could take) / (time it took), and which bound
    sets that least time: ``"memory"`` or ``"compute"``."""
    t_mem = total_bytes / peak["hbm_bytes_per_s"]
    t_flop = total_flops / peak["flops_per_s"]
    bound = "memory" if t_mem >= t_flop else "compute"
    return max(t_mem, t_flop) / seconds, bound
