"""Published chip-to-chip peaks by exact ``device_kind``, and how many of a
chip's ports a host's wiring uses: what a collective's wire bytes are held
against, as ``harness/peaks.py`` holds a kernel's operations and bytes
against the chip's own peaks."""

from __future__ import annotations

from typing import Dict, NamedTuple


class WirePeaks(NamedTuple):
    kind: str
    ports: int                 # chip-to-chip ports of one chip
    bytes_per_s_a_port: float  # one direction


# Source: Google Cloud documentation, "TPU v5e" system architecture: 1,600
# Gbit/s of chip-to-chip interconnect a chip, over the four ports of a 2D
# torus: 400 Gbit/s = 50 GB/s a port.
WIRES: Dict[str, WirePeaks] = {w.kind: w for w in (
    WirePeaks("TPU v5 lite", 4, 50e9),
)}

# A 2x2 host (four chips, no wrap-around: a wrap would reach the same
# neighbour again) wires two of a chip's four ports, one a neighbour.
PORTS_WIRED = {4: 2}


def send_peak(kind: str, chips: int) -> Dict:
    """The most bytes a second one chip can put on the wires of a host of
    ``chips`` chips. A device or a host that is not in the tables is an
    error, not a default."""
    try:
        w, ports = WIRES[kind], PORTS_WIRED[chips]
    except KeyError:
        raise LookupError(
            f"no published wire peak for device_kind {kind!r} on a host of "
            f"{chips} chips (known: {sorted(WIRES)}, {sorted(PORTS_WIRED)} "
            "chips)") from None
    return {"ports_wired": ports, "ports": w.ports,
            "bytes_per_s_a_port": w.bytes_per_s_a_port,
            "bytes_per_s": ports * w.bytes_per_s_a_port}
