"""What JAX says about the device: platform, kind, count, and the
allocator's own high-water marks."""

from __future__ import annotations

from typing import Dict


class NoAccelerator(RuntimeError):
    pass


def device_info() -> Dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> Dict:
    """The device facts, or NoAccelerator when this is not a TPU host
    with at least ``chips`` chips: a measurement never falls back."""
    info = device_info()
    if info["platform"] != "tpu":
        raise NoAccelerator(f"platform is {info['platform']!r}, not 'tpu'")
    if info["count"] < chips:
        raise NoAccelerator(f"{info['count']} chip(s), the cell needs {chips}")
    return info


def memory_by_device() -> list:
    """``memory_stats()`` of every device, as reported. On a TPU,
    ``peak_bytes_in_use`` counts live buffers only; the temporaries of a
    running program sit in the allocator's reserved pool, so the chip's
    high-water mark is the two peaks together (``peak_bytes``)."""
    import jax
    out = []
    for d in jax.devices():
        s = d.memory_stats() or {}
        row = {k: int(s[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_reserved", "peak_bytes_reserved",
                                      "bytes_limit") if k in s}
        row["device"] = str(d)
        row["peak_bytes"] = (row.get("peak_bytes_in_use", 0)
                             + row.get("peak_bytes_reserved", 0))
        out.append(row)
    return out


def fullest(memory: list) -> Dict:
    return max(memory, key=lambda r: r["peak_bytes"])
