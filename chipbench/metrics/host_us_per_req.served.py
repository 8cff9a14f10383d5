"""Host time in the front end per request: the benchmark's spans around
``submit`` and around each ``pump`` that closed or collected a batch,
summed over the window, over the requests the front end served."""
import numpy as np


def read(run):
    served = run.counters.get("stream_served_total")
    if not served:
        return None
    busy = float(np.sum(run.spans["submit"]) + np.sum(run.spans["pump_working"]))
    return 1e6 * busy / served
