"""The work a search needs, from the configuration's shapes and the
measured hop counts, and the least time the chip could take for it.

Counted for the algorithm, not for an implementation: what a different
kernel happens to move (views, padding, copies) does not change it.

Per query, for a Vamana graph of degree R searched with frontier L over
D-dimensional rows with b-bit RaBitQ codes:

* bytes: hops x (R x 4 B of adjacency + R x code bytes) + L x D x 4 B
  of f32 rows for the exact rerank, where code bytes are ceil(D x b / 8)
  packed bits plus two f32 correction factors;
* operations: hops x R x 2D for the estimator's dot products, plus
  L x 3D for the rerank's squared differences.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown kind is
    an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def code_bytes(dims: int, bits: int) -> int:
    return math.ceil(dims * bits / 8) + 8


def search_bytes(hops: float, *, dims: int, bits: int, degree: int,
                 beam: int) -> float:
    """Bytes one query's search needs to read."""
    return (hops * (degree * 4 + degree * code_bytes(dims, bits))
            + beam * dims * 4)


def search_flops(hops: float, *, dims: int, degree: int, beam: int
                 ) -> float:
    return hops * degree * 2 * dims + beam * 3 * dims


def least_time(total_bytes: float, total_flops: float, peak: dict
               ) -> tuple[float, str]:
    """(seconds, bounding roof) of the work at the chip's peaks."""
    t_mem = total_bytes / peak["hbm_bytes_per_s"]
    t_ops = total_flops / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "bf16_flops")
