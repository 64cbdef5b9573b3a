"""The chip's peak bytes in use (``memory_stats()['peak_bytes_in_use']``,
read after the window) over the cell's HBM budget, as a ratio."""


def read(w):
    if not w.peak_bytes:
        return None
    return w.peak_bytes / w.budget_bytes
