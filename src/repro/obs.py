"""Names of the simulator's traced stages and host spans.

``stage(name)`` is a ``jax.named_scope``: it tags every op traced inside
it, and the TPU profiler keeps the tag in each device op's name stack
(the innermost ``repro.*`` component names the op's stage).  It changes
only HLO metadata, so it costs nothing at run time.  ``span(name)`` is a
``jax.profiler.TraceAnnotation`` around host work; with no profiler
running it costs well under a microsecond.

The chip benchmark reads the device idle time under each host span as
per-layer metrics (``benchmarks/chip/metrics/*_idle_ms_per_chunk.py``).
Its reduction does not keep an op's stage, so the per-stage device time
per window comes from ``benchmarks/chip/stage_split.py`` for now.
"""
from __future__ import annotations

import jax

# device stages, outermost first where they nest: install inside switch,
# key_version and sketch inside server
STAGES = (
    "repro.gen",           # client generation (Zipf search) and ingress concat
    "repro.switch",        # the switch pass: window_pipeline or the baseline scans
    "repro.install",       # the per-window orbit value-byte install
    "repro.server",        # server FIFOs: enqueue, serve, reply lanes
    "repro.key_version",   # the servers' key_version update and read
    "repro.sketch",        # the servers' popularity tracking (count-min sketch)
    "repro.account",       # client accounting, pending replies, window metrics
    "repro.controller",    # the traced control-plane update
)

# host spans of the batched fleet's chunk loop
SPANS = (
    "repro.fetch",         # device-to-host copies after a chunk
    "repro.churn",         # the hot-in swap and the workload re-stack
)


def stage(name: str):
    """A named scope for a device stage of :data:`STAGES`."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {STAGES}")
    return jax.named_scope(name)


def span(name: str):
    """A profiler annotation for a host span of :data:`SPANS`."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; spans are {SPANS}")
    return jax.profiler.TraceAnnotation(name)
