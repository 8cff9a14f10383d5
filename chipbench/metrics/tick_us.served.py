"""Host time of the lifecycle tick (detector poll, event application,
repairs) per served dispatch, from the program's ``lifecycle_tick`` spans."""
import program_spans


def read(run):
    return program_spans.per_dispatch_us(run, "lifecycle_tick")
