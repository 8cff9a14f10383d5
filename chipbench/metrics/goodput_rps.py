"""Requests answered within the configuration's latency limit of when they
were due, over the window's seconds."""


def read(run):
    if "on_time" not in run.window:
        return None
    return run.window["on_time"] / run.window["seconds"]
