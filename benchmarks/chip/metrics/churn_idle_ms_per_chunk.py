"""Device idle time per timed chunk under the program's ``repro.churn``
host span, in milliseconds: the hot-in swap (host permutation and its
upload) and the fleet's workload re-stack.  0 for a program without the
span."""
import stages


def read(ctx):
    return stages.idle_ms_per_chunk(ctx, "repro.churn")
