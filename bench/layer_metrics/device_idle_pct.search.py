"""Share of the traced window of a search cell in which no op ran on the
device: 1 minus the union of device op intervals over the window."""
from bench import trace


def read(run):
    return trace.idle_pct(run["trace"])
