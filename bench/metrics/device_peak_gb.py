"""The largest ``peak_bytes_in_use`` of the cell's devices after the
window, in GB (1e9 bytes)."""


def read(run):
    peaks = [p for p in run.peak_bytes if p]
    return max(peaks) / 1e9 if peaks else None
