"""Seconds from process start to the first timed operation: loading,
building the fleet and its inputs, compiling or fetching every program the
cell uses, and the warm-up."""


def read(run):
    return run.setup_s
