"""Engine: mean wall time of a flush's engine call, host to device and
back (``FlushRecord.kernel_s``), ms."""


def read(m):
    if not m.flushes:
        return None
    return 1e3 * sum(f.kernel_s for f in m.flushes) / len(m.flushes)
