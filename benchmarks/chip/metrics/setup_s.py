"""Process start to window start: import, TPU init, weights, crossbar programming, warm-up, inputs."""
def read(run):
    return run.setup_s
