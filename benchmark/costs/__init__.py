"""Operations and bytes that a step *needs*, from the configuration and
the live batch: the yardstick's side of every roofline share."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(os.path.dirname(_HERE), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]


def roofline_seconds(flops: float, bytes_: float, device_kind: str) -> dict:
    """Least time the chip could take, and which peak bounds it."""
    p = peaks(device_kind)
    t_c, t_m = flops / p["bf16_flops_per_s"], bytes_ / p["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory"}
