"""Model FLOPs of the prompt and output tokens the traced slice
processed, over the slice's length times the chip's bf16 peak."""
from harness.readings import model_flops


def read(run):
    lo, hi = run.trace.window()
    flops = model_flops(run)
    if not flops:
        return None
    return 100.0 * flops / ((hi - lo) / 1e9 * run.peaks["bf16_flops"])
