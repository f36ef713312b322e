"""The most the backend's allocator held on the fullest device since the
process started: live arrays (`peak_bytes_in_use`) plus what loaded
programs keep for their temporaries (`peak_bytes_reserved`). Both are
peaks over the process's life, set-up included, and need not fall
together, so the sum is an upper bound on the peak and not the step's own
need. In GB (10^9 bytes). No args."""


def read(args: dict, r: dict):
    peak = r.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
