"""Host time of the eager layout executables around the route program per
bulk call, from the program's ``route.layout`` spans (the reshape in and
out, or the sharded route's pad, upload, donation copy and output slice):
what fusing the layout into the route program would remove."""
import program_spans


def read(run):
    return program_spans.per_call_us(run, "route.layout")
