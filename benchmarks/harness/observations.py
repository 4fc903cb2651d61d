"""What one run hands to the metric readers (``metrics/<metric>.py``)."""

import dataclasses
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class Observations:
    kind: str                      # the traffic file's "kind"
    cell: Any                      # cells.Cell: configuration, traffic, names
    devices: list
    window: Tuple[float, float] = (0.0, 0.0)   # measured window, monotonic s
    setup_end: Optional[float] = None          # first instant of the window
    process_start: float = 0.0                 # monotonic s, set by run.py
    correct: bool = False
    attempted: int = 0
    failed: int = 0
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    # serving: one record per request (see run_serve.Record)
    requests: List[Any] = dataclasses.field(default_factory=list)
    # serving, traced runs: one (t0, t1, counters, decode contexts) per tick
    ticks: List[Any] = dataclasses.field(default_factory=list)
    # host intervals (monotonic s) to leave out of host-side metrics: the
    # profiler's start and stop stall the process
    host_exclude: List[Tuple[float, float]] = dataclasses.field(default_factory=list)
    train: Dict[str, Any] = dataclasses.field(default_factory=dict)
    spans: List[Any] = dataclasses.field(default_factory=list)   # trace.Span, program's tracer
    trace: Optional[Any] = None    # trace.Trace of the profiled sub-window

    @property
    def device_kind(self) -> str:
        return self.devices[0].device_kind

    def outside_stall(self, t: float) -> bool:
        return not any(a <= t <= b for a, b in self.host_exclude)
