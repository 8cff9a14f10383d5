"""Host time of one bulk call (route_keys or place_keys), from the
benchmark's own span around it: the mean over the window's calls."""
import numpy as np


def read(run):
    spans = run.spans.get("dispatch")
    if spans is None or not len(spans):
        return None
    return 1e6 * float(np.mean(spans))
