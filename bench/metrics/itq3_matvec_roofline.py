"""Share of its roofline the ITQ3_S matvec kernel (decode at M <= 16
slots) reached in the traced slice: the least time the decode steps'
projections need (planes and scales once, or FLOPs, over the peak) over
the kernel's device time."""
from harness.readings import itq3_roofline


def read(run):
    return itq3_roofline(run, r"itq3_matvec_pallas")
