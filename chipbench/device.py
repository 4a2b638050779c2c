"""The chips a run may use and their published peaks (peaks.json)."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    """Peak FLOP/s and HBM bytes/s of one chip of this device_kind; a kind
    that is not in the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]


def chips(n: int):
    """The first n TPU chips, or NoChip."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs {n} TPU chip(s); JAX found platform {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"needs {n} TPU chip(s); JAX found {len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
