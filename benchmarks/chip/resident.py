"""What the program keeps resident on the chip."""

from __future__ import annotations


def device_bytes(arrays) -> int:
    """Bytes the arrays hold on their device, layout padding included."""
    return sum(int(a.on_device_size_in_bytes()) for a in arrays)


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device, or None where the
    backend keeps no such statistic."""
    stats = [d.memory_stats() for d in devices]
    peaks = [int(s["peak_bytes_in_use"]) for s in stats if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None
