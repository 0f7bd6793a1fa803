"""Share of the traced slice in which no operation ran on the device."""
from harness.trace import idle_share


def read(run):
    return 100.0 * idle_share(run.trace)
