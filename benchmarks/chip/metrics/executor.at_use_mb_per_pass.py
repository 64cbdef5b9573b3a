"""Bytes the executor fetched synchronously at use for placements on the
CPU engine (``ExecStats.at_use_bytes``), per pass over the plan (a decode
pass or a layer-major prefill), in MB of 10**6 bytes."""


def read(w):
    c = w.counters
    passes = c["decode_passes"] + c["prefill_passes"]
    if not passes or not c["at_use_bytes"]:
        return None
    return c["at_use_bytes"] / passes / 1e6
