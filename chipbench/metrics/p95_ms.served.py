"""95th percentile (nearest rank) of every request due in the window, from
when it was due to when the front end handed back its answer.  A request
shed or never answered counts as answered only when the run gave up on
it, after the window and the drain.  Per layer, not end to end: a host
freeze of a few seconds moves it by hundreds of times."""
import math

import numpy as np


def read(run):
    lat = run.latencies_ms
    if lat is None or not lat.size:
        return None
    return float(np.sort(lat)[math.ceil(0.95 * lat.size) - 1])
