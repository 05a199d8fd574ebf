"""Find everything a cell needs by name: ``BENCHMARK.json`` at the root of
the checkout, ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py``, ``kernels/<kernel>.py``, ``schemes/<scheme>.py``
and ``peaks.json``.

A later change adds a cell with new files and one entry in
``BENCHMARK.json``; nothing here names a cell, a configuration or a mix.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from typing import NamedTuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Cell(NamedTuple):
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list      # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return json.loads(path.read_text())


def config(name: str) -> dict:
    return _json("configs", name)


def traffic(name: str) -> dict:
    return _json("traffic", name)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, b: dict | None = None) -> Cell:
    b = b or bench()
    entry = next((w for w in b["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}")
    return Cell(
        name=name, config=config(entry["config"]),
        traffic=traffic(entry["traffic"]), chips=int(entry["chips"]),
        end_to_end=[m for m in b["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in b["per_layer"] if _reports(m, name)])


def peaks(device_kind: str) -> dict:
    """Peak rates of a device kind; a kind not in the table is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def _module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``metrics/<name>.py``: ``read(ctx) -> float | None``."""
    return _module("metrics", name)


@functools.lru_cache(maxsize=None)
def scheme(name: str):
    """``schemes/<name>.py``: the reference's switch for a configuration's
    ``rack.scheme`` and where the program keeps its state (see
    ``reference.py``)."""
    return _module("schemes", name)


def kernel_counter(name: str):
    """``kernels/<name>.py``: ``TRACE_NAMES`` and ``per_window(shapes) ->
    dict(ops, bytes, ops_peak) | None``."""
    return _module("kernels", name)


def shapes(c: Cell) -> dict:
    """Logical shapes of one fleet window of the cell, as the program's
    kernel dispatchers see them (unpadded)."""
    from reference import CMS_DEPTH, CMS_WIDTH, CRN_WIDTH, K_REPORT, geometry

    g = geometry(c.config["rack"], c.config["workload"]["key_size"])
    r = g.subrounds
    reply = g.n_servers * g.cap
    lanes = (g.client_batch + CRN_WIDTH + reply + (-reply) % r + g.fetch_lanes) // r
    return dict(
        points=len(c.traffic["offered_rps"]), subrounds=r, lanes=lanes,
        entries=g.entries, queue=g.queue, frags=1, serves=g.max_serves,
        servers=g.n_servers, cms_depth=CMS_DEPTH, cms_width=CMS_WIDTH,
        report_lanes=g.n_servers * K_REPORT, scheme=g.scheme,
        track=bool(c.traffic.get("track_popularity")),
        period=c.traffic.get("controller_period_windows"))
