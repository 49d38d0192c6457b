"""Seconds from the start of the process to the moment the engine was ready
to serve the traffic: imports, weights, plan, compiling or loading the
programs, warm-up."""


def read(run):
    return run.setup_s
