"""95th percentile of the gaps between consecutive tokens of every
request, as the client saw them, over the window."""
from harness.readings import p95_ms, window_gaps


def read(run):
    return p95_ms(window_gaps(run))
