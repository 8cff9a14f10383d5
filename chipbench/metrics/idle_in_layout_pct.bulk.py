"""Share of the traced window in which the idlest chip sat idle while the
host was inside a ``route.layout`` span, the spans shifted onto the chip's
clock by the offset ``program_spans`` finds: the idle that fusing the
layout into the route program could reclaim.  At most ``idle_pct.bulk``."""
import program_spans


def read(run):
    found = program_spans.of_run(run)
    if found is None:
        return None
    return program_spans.idle_in_pct(run.trace, found, "route.layout")
