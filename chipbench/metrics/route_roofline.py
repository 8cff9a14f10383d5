"""The fused route kernel's share of its HBM roofline: the least time the
bytes of one call's keys on one chip take at the chip's HBM bandwidth,
over the kernel's device time per call on the slowest chip."""
import work


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    each = run.trace.slowest_per_call_s(work.ROUTE_KERNEL, run.window.get("calls"))
    if each is None:
        return None
    least = work.route_bytes(run.window["keys_per_device_call"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / each
