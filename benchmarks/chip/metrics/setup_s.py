"""Process start to the window's opening: JAX start-up, building the fleet
and the server, tracing and compiling, and the warm-up."""


def read(run):
    return run.setup_s
