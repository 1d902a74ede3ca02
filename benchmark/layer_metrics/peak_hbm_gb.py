"""Device: the result line's ``memory_peak_bytes`` in GB (1e9 bytes).
Derived, not read: the allocator's ``peak_bytes_in_use`` on the fullest
chip plus the largest loaded program's temporaries (``lib/device.py``),
an upper estimate of the peak."""


def read(ctx):
    peak = ctx.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
