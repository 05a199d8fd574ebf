"""A cell of the benchmark cut to a size a CPU test run can hold.

Same configuration and traffic files, same harness and reference; only
the scale is cut: 5,000 keys, 4 servers, 64 request lanes, 16 cache
entries, 2 sweep points and 32-window chunks (8-window control periods).
With ``points=None`` the cell keeps its mix's own sweep points and
``check_points``, each load scaled by the servers kept (4 of 32).
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
for p in (HERE.parent, HERE.parents[2] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import layout  # noqa: E402


def tiny_cell(config: str, traffic: str, points: int | None = 2,
              chunk: int = 32) -> layout.Cell:
    c, t = layout.config(config), layout.traffic(traffic)
    c["workload"]["num_keys"] = 5000
    scale = 4 / c["rack"]["num_servers"]
    c["rack"].update(num_servers=4, client_batch=64, cache_entries=16, fetch_lanes=32)
    if points is None:
        t["offered_rps"] = [r * scale for r in t["offered_rps"]]
    else:
        t["offered_rps"] = [2e5 + 4e5 * i / max(points - 1, 1) for i in range(points)]
    t["chunk_windows"] = chunk
    if t.get("controller_period_windows"):
        t["controller_period_windows"] = chunk // 4
    if t.get("churn_swap"):
        t["churn_swap"] = 4
    b = layout.bench()
    return layout.Cell(name=f"tiny.{config}.{traffic}", config=c, traffic=t, chips=1,
                       end_to_end=b["end_to_end"], per_layer=[])
