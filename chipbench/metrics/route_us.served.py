"""Host time of a served batch's upload and route call per served dispatch,
from the program's ``upload`` and ``route.call`` spans."""
import program_spans


def read(run):
    return program_spans.per_dispatch_us(run, "upload", "route.call")
