"""The simulator's named stages and host spans (``repro.obs``).

Every stage tags ops of the compiled chunks where it belongs (read from
the compiled HLO's ``op_name`` metadata, which the TPU profiler keeps as
each device op's name stack), the fleet's host loop emits its spans into
a profiler trace, and unknown names are refused.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kvstore.fleet import (
    BatchedRackSimulator,
    compiled_batched_chunk,
    compiled_batched_controller_chunk,
)
from repro.kvstore.simulator import RackConfig
from repro.kvstore.workload import Workload, WorkloadConfig
from repro.launch.hlo_analysis import parse_computations

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_STAGE = re.compile(r"repro\.[A-Za-z0-9_]+")

WINDOW_STAGES = {"repro.gen", "repro.switch", "repro.server",
                 "repro.key_version", "repro.account"}


def _fleet(scheme: str, track: bool) -> BatchedRackSimulator:
    cfg = RackConfig(scheme=scheme, cache_entries=16, num_servers=4,
                     client_batch=64, fetch_lanes=32, track_popularity=track)
    wl = Workload(WorkloadConfig(num_keys=5000, offered_rps=2e5))
    return BatchedRackSimulator(cfg, wl, offered_rps=[2e5, 4e5], seeds=[1, 2])


def _stages_in(compiled_hlo: str) -> set[str]:
    """The innermost ``repro.*`` component of every instruction's op_name."""
    found = set()
    for comp in parse_computations(compiled_hlo).values():
        for inst in comp.instructions:
            m = _OP_NAME.search(inst.rest)
            names = _STAGE.findall(m.group(1)) if m else []
            if names:
                found.add(names[-1])
    return found


@pytest.mark.parametrize("scheme,program,want", [
    ("orbitcache", "chunk",
     WINDOW_STAGES | {"repro.install", "repro.sketch"}),
    ("orbitcache", "controller", set(obs.STAGES)),
    ("nocache", "chunk", WINDOW_STAGES),
])
def test_every_stage_tags_the_compiled_chunk(scheme, program, want):
    f = _fleet(scheme, track=program == "controller" or scheme == "orbitcache")
    if program == "chunk":
        fn = compiled_batched_chunk(f.cfg, f.server_cfg, f.client_cfg,
                                    f.key_size, 4, f._wl_axes)
        lowered = fn.lower(f._wl, f.carry)
    else:
        fn = compiled_batched_controller_chunk(
            f.cfg, f.controllers[0].cfg, f.server_cfg, f.client_cfg,
            f.key_size, 2, 2, f._wl_axes)
        lowered = fn.lower(f._wl, f.carry,
                           jnp.full((f.n_points,), 16, jnp.int32))
    found = _stages_in(lowered.compile().as_text())
    assert want <= found, sorted(want - found)
    assert found <= set(obs.STAGES), sorted(found - set(obs.STAGES))
    if scheme == "nocache":
        assert "repro.install" not in found


def test_host_spans_reach_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    f = _fleet("orbitcache", track=True)
    f.preload()
    f.run(8 * 100e-6, chunk_windows=8, controller_period_s=2 * 100e-6)
    with jax.profiler.trace(str(tmp_path)):
        f.workloads[0].hot_in_swap(4)
        f.refresh_workloads()
        res = f.run(8 * 100e-6, chunk_windows=8,
                    controller_period_s=2 * 100e-6)
    assert np.sum(res[0].traces["tx"]) > 0
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    names = {e.name for plane in pd.planes if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events}
    assert set(obs.SPANS) <= names


@pytest.mark.parametrize("helper,name", [
    (obs.stage, "repro.nope"),
    (obs.stage, "repro.fetch"),      # a span is not a stage
    (obs.span, "repro.gen"),         # a stage is not a span
    (obs.span, "fetch"),
])
def test_unknown_names_are_refused(helper, name):
    with pytest.raises(ValueError, match="unknown"):
        helper(name)
