"""What a per-layer metric reader is given: the run's window, the program's
per-request counters, compile events and, in a traced run, the trace."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from perfbench.trace import Trace
from perfbench.window import Window


@dataclasses.dataclass
class RunData:
    cell: str
    cfg: Dict[str, Any]                  # the configuration's model block
    serving: Dict[str, Any]              # the configuration's serving block
    peaks: Dict[str, Any]
    sizes: List[Tuple[int, int]]         # (prompt_len, max_new) per request
    window: Window
    # per request that started in the window (finished or in flight at its
    # close): GenerationResult.metrics as a dict
    counters: Dict[int, Dict[str, float]]
    compiles: List[Tuple[float, float, str]]   # executables obtained in it
    trace: Optional[Trace] = None
    trace_window_s: Optional[float] = None

    def total(self, key: str) -> float:
        return sum(c[key] for c in self.counters.values())

    def ratio(self, num: str, den: str) -> Optional[float]:
        d = self.total(den)
        return self.total(num) / d if d else None
