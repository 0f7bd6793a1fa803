"""Share of its roofline the tiled ITQ3_S matmul kernel reached in the
decode steps of the traced slice (decode at M > 16 slots runs it): the
least time the steps' projections need (planes and scales once, or
FLOPs, over the peak) over the kernel's device time inside decode
programs."""
from harness.readings import itq3_roofline


def read(run):
    return itq3_roofline(run, r"itq3_matmul_pallas")
