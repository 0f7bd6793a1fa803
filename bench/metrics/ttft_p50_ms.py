"""Median, over every request due in the window, of due time to first
token at the client. A request that never got one counts as infinitely
late."""
import numpy as np


def read(run):
    ttft = [r.times[0] - r.due if r.times else float("inf")
            for r in run.records if r.req.in_window]
    if not ttft:
        return None
    v = float(np.percentile(ttft, 50) * 1e3)
    return v if v != float("inf") else None
