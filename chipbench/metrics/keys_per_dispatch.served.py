"""Keys in one device dispatch of the front end: the change in the
program's ``stream_served_total`` over the change in
``stream_dispatches_total`` across the window."""


def read(run):
    served = run.counters.get("stream_served_total")
    dispatches = run.counters.get("stream_dispatches_total")
    if not served or not dispatches:
        return None
    return served / dispatches
