"""Device time of the fused route kernel per call, from the trace: on four
chips, the slowest chip's."""
import work


def read(run):
    if run.trace is None:
        return None
    each = run.trace.slowest_per_call_s(work.ROUTE_KERNEL, run.window.get("calls"))
    return None if each is None else 1e3 * each
