"""Device time of the decode program in the traced slice, per decode
step."""
from harness.readings import DECODE_PROGRAM, decode_steps
from harness.trace import time_ns


def read(run):
    n = decode_steps(run)
    t = time_ns(run.trace.programs, DECODE_PROGRAM)
    return t / n / 1e6 if n and t else None
