"""95th percentile of due time to admission (``Request.t_admit``, the
engine's stamp on the same perf_counter clock) over the window's
requests: the time a request waits before its prefill starts."""
from harness.readings import p95_ms


def read(run):
    waits = [r.engine_req.t_admit - r.due for r in run.records
             if r.req.in_window and r.engine_req is not None
             and r.engine_req.t_admit is not None]
    return p95_ms(waits)
