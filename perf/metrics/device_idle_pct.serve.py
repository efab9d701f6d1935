"""Share of the traced serving call in which the device runs no operation,
from the profiler trace (%): 100 * (1 - busy / window)."""


def read(d):
    if not d.reduction.devices:
        return None
    return d.reduction.idle_pct
