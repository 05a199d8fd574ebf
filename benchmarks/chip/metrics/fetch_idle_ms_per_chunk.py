"""Device idle time per timed chunk under the program's ``repro.fetch``
host span, in milliseconds: the device-to-host copies after each chunk
(traces, the per-point active sizes, the controller's last update,
histograms).  0 for a program without the span."""
import stages


def read(ctx):
    return stages.idle_ms_per_chunk(ctx, "repro.fetch")
