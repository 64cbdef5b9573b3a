"""Streamed-copy time the compute waited on (``copy_s_exposed``), per
pass over the plan (a decode pass or a layer-major prefill), in ms."""


def read(w):
    c = w.counters
    passes = c["decode_passes"] + c["prefill_passes"]
    if not passes or not c["streamed_bytes"]:
        return None
    return 1e3 * c["copy_s_exposed"] / passes
