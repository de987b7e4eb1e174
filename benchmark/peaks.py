"""The chip's published peaks, from ``peaks.json``, by ``device_kind``.

A device that is not in the table is an error, never a default."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def of(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in peaks.json")
    return table[device_kind]


def product_peak(device_kind: str, operand) -> float:
    """Peak rate of the products whose left operand has element type
    ``operand``: int8 products against the int8 peak, all others (bf16,
    and f32, which the MXU runs as bf16 passes) against the bf16 peak."""
    p = of(device_kind)
    if operand in ("s8", "u8"):
        return p["int8_ops_per_s"]
    return p["bf16_flops_per_s"]
