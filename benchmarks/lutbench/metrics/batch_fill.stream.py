"""Micro-batcher: mean real rows per flush (``FlushRecord.fill``)."""


def read(m):
    if not m.flushes:
        return None
    return sum(f.fill for f in m.flushes) / len(m.flushes)
