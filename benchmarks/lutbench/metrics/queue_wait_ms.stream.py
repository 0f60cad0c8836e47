"""Micro-batcher: mean queueing delay of each flush's oldest request
(``FlushRecord.waited_s``), ms."""


def read(m):
    if not m.flushes:
        return None
    return 1e3 * sum(f.waited_s for f in m.flushes) / len(m.flushes)
