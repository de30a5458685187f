"""The chip a run measures: which it is, its published peaks, and its
peak memory. A run without a TPU, with fewer chips than the cell asks
for, or on a chip missing from ``bench/peaks.json`` fails; it never
falls back to the CPU."""
from __future__ import annotations

import json
import os


class NoChip(RuntimeError):
    """The run cannot measure: no TPU, too few chips, or an unknown one."""


def peaks_table(bench_dir: str) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        return json.load(f)


def peaks_for(kind: str, table: dict) -> dict:
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in the peak table "
                     f"(known: {sorted(table)})")
    return table[kind]


def require_chip(chips: int, table: dict) -> dict:
    """The devices JAX found, as the result line names them."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    kind = devices[0].device_kind
    peaks_for(kind, table)
    return {"platform": platform, "kind": kind, "count": chips}


def memory_peak_bytes(chips: int) -> int | None:
    """``peak_bytes_in_use`` on the fullest of the chips used."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
