"""Bulk calls (route_keys or place_keys) whose host dispatch took over
50 ms, per minute of window: the chip idles through each."""


def read(run):
    return 60.0 * run.window["dispatches_over_50ms"] / run.window["seconds"]
