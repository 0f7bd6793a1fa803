"""Every output token that reached the client in the window, over the
window."""
from harness.readings import window_tokens


def read(run):
    return window_tokens(run) / run.window_s
