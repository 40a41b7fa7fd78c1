"""Share of the window in which no op ran on the device, in %, from the
profiler trace (1 - union of op intervals / window).  The reader of
``device_idle.bulk``, ``device_idle.open`` and ``device_idle.read``."""
from bench import work


def read(run):
    return work.idle_pct(run)
