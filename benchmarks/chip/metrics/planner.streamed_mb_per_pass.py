"""Weight bytes the plan streamed over the host link per decode pass
(``ExecStats.pass_streamed_bytes``), in MB of 10**6 bytes."""


def read(w):
    passes = w.decode_pass_streamed
    if not passes or not sum(passes):
        return None
    return sum(passes) / len(passes) / 1e6
