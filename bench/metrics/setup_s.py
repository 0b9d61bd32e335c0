"""Process start to the window's start: imports, device start-up, state
and traffic from the seed, compile or compile-cache loads, warm-up."""


def read(run):
    return run.setup_s
