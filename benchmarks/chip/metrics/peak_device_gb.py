"""The largest ``memory_stats()["peak_bytes_in_use"]`` of the cell's devices
after the window, over 1e9. The counter may leave a program's temporaries
out (PERF.md)."""

UNIT = "GB"
SOURCE = "host_clock"


def read(record: dict):
    peaks = [p for p in record["peak_bytes"] if p]
    return max(peaks) / 1e9 if peaks else None
