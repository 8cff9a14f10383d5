"""Share of the window's placement columns that the zone fallback moved to
another zone, in percent: the change in the program's
``placement_zone_fallback_columns_total`` over the change in
``placement_columns_total``.  A program without the counters reads
nothing."""


def read(run):
    moved = run.counters.get("placement_zone_fallback_columns_total")
    columns = run.counters.get("placement_columns_total")
    if moved is None or not columns:
        return None
    return 100.0 * moved / columns
