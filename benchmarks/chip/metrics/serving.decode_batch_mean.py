"""Mean fused decode batch: tokens of requests past their first, per
``step()`` that decoded, from the TokenEvents each step returned."""


def read(w):
    batches = [s.decode_tokens for s in w.steps if s.decode_tokens]
    return sum(batches) / len(batches) if batches else None
