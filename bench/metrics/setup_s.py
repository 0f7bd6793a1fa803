"""Process start to window start: loading, weights, engine, warm-up."""


def read(run):
    return run.setup_s
