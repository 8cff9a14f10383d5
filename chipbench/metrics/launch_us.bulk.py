"""Host time of the enqueue of the route program per bulk call, from the
program's ``route.launch`` spans: ``route_2d``, ``_route_replicas_jit`` or
the shard_map executable, and any block on the runtime's in-flight limit."""
import program_spans


def read(run):
    return program_spans.per_call_us(run, "route.launch")
