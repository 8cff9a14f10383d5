"""Turns of the serving loop (heartbeats, submits and one ``pump``) that
took over 50 ms, per minute of window: each such stall makes every request
due during it late, and sheds those past their deadline."""


def read(run):
    return 60.0 * run.window["loop_turns_over_50ms"] / run.window["seconds"]
