"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window."""
from harness.readers import idle_pct


def read(run):
    return idle_pct(run)
