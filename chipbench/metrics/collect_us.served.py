"""Host time waiting on the device's result per served dispatch, from the
program's ``collect`` spans."""
import program_spans


def read(run):
    return program_spans.per_dispatch_us(run, "collect")
