"""Set-up: from the start of the command to the opening of the window, on
the wall clock (two processes): launcher and agent start, JAX start,
trace+lower, compile or cache load, weights on the device, warm-up steps."""


def read(run: dict):
    return run["window"]["opened_wall"] - run["started_wall"]
