"""Device idle time per timed chunk under no ``repro.*`` host span, in
milliseconds: the harness's own work between chunks and dispatch.  With
``fetch_idle_ms_per_chunk`` and ``churn_idle_ms_per_chunk`` it adds up to
the traced window's idle time; for a program without spans it is all of
it."""
import stages


def read(ctx):
    return stages.idle_ms_per_chunk(ctx, "")
