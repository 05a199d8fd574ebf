"""Distributed key-value store substrate: stores, servers, clients, workloads,
and the discrete-time rack simulator used by the paper's evaluation."""
from .workload import WorkloadConfig, Workload, WorkloadArrays  # noqa: F401
from .simulator import RackConfig  # noqa: F401
from .fabric_sim import FabricConfig  # noqa: F401
from .fleet import (  # noqa: F401
    BatchedFabricSimulator,
    BatchedRackSimulator,
    FabricSimulator,
    RackSimulator,
)
