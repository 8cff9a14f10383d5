"""Mean host time of one pass of the breakers' loop, from the program's
``breakers.observe`` spans (one a pump)."""
import program_spans


def read(run):
    found = program_spans.of_run(run)
    if found is None or not found.of("breakers.observe"):
        return None
    return found.total_us("breakers.observe") / len(found.of("breakers.observe"))
