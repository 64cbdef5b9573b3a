"""Host-to-device rate the prefetcher reached: bytes it staged over the
seconds its copies took (hidden plus exposed), in GB/s of 10**9 bytes."""


def read(w):
    c = w.counters
    if not c["prefetch_bytes"] or c["prefetch_copy_s"] <= 0:
        return None
    return c["prefetch_bytes"] / c["prefetch_copy_s"] / 1e9
