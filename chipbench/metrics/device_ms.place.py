"""Device time of the placement program per call, from the trace."""
import reduction
import work


def read(run):
    if run.trace is None:
        return None
    each = run.trace.slowest_per_call_s(work.PLACE_PROGRAM, run.window.get("calls"),
                                        reduction.MODULES_LINE)
    return None if each is None else 1e3 * each
