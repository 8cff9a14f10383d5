"""Mean wait of a served request in the open batch: the sum of the
``wait_us_sum`` tags of the program's ``dispatch`` spans over the sum of
their ``size`` tags (dispatch start minus arrival, per kept request)."""
import program_spans


def read(run):
    found = program_spans.of_run(run)
    if found is None:
        return None
    tags = found.served_dispatches()
    size = sum(t["size"] for t in tags)
    return sum(t["wait_us_sum"] for t in tags) / size if size else None
