"""Bytes and least times of an allreduce, computed from its shapes.

The yardstick for the ring kernel's roofline and for bus bandwidth. Bus
bandwidth follows nccl-tests' convention (doc/PERFORMANCE.md): an
allreduce of N bytes per rank over P ranks moves 2(P-1)/P * N bytes
through each rank's links, whatever the algorithm.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip of `device_kind`. A kind that is not
    in the table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS_FILE.name}")
    return table[device_kind]


def bus_bytes(nbytes: int, world: int) -> float:
    """Bytes one rank's links carry in an allreduce of `nbytes` per rank."""
    return 2.0 * (world - 1) / world * nbytes


def hbm_bytes(nbytes: int) -> float:
    """Least HBM traffic of one rank: read the input once, write the
    result once."""
    return 2.0 * nbytes


def least_time_s(nbytes: int, world: int, peaks: dict) -> tuple[float, str]:
    """The least time one chip could take for its part of the allreduce,
    and which bound sets it: `ici` (wire bytes over the interconnect
    peak) or `hbm` (bytes over the memory peak)."""
    t_ici = bus_bytes(nbytes, world) / (peaks["ici_bits_per_s"] / 8.0)
    t_hbm = hbm_bytes(nbytes) / peaks["hbm_bytes_per_s"]
    return (t_ici, "ici") if t_ici >= t_hbm else (t_hbm, "hbm")
