"""Per-layer metric readers.  Each metric named in BENCHMARK.json has a
file `benchmark/metrics/<name>.json`: {"reader": <module here>, "params":
{...}}.  A reader is `read(ctx, **params)` and returns the value, or None
when the run gave it nothing to read (the metric is then left out)."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List


@dataclasses.dataclass
class ReadContext:
    cell: Dict
    config: Dict
    traffic: Dict
    peaks: Dict
    chips: int
    counters: Dict[str, float]       # deltas over the measured window
    samples: Dict[str, List[float]]  # per request or per step
    trace: Any                       # benchmark.reduce.xplane.Reduced
    memory_peak_bytes: int
