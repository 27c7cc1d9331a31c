"""What a metric reader sees of one run.

A reader is ``metrics/<metric>.py`` with ``read(ctx) -> float | None``;
``None`` means it found nothing to read in this run, and the metric is
left out of the result line.  End-to-end readers use the window's host
clock; per-layer readers the trace (``ctx.trace``), the costs of the
shared kernels (``costs``) and the configuration's model family
(``ctx.family``) for the work of the architecture.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional

import numpy as np

import costs
import xplane
from cell import WindowResult, load_module, row_len

# the jitted serve step, by the name of the program's module for it
STEP_MODULE = r"serve_step"


@dataclasses.dataclass
class Ctx:
    cell: Dict[str, Any]
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    win: WindowResult
    setup_s: float
    device_kind: str
    trace: Optional[xplane.Trace] = None
    family: Any = None               # the configuration's (cell.family)

    # ---- host clock ------------------------------------------------------

    def due_in_window(self):
        return [r for r in self.win.records if r.due < self.win.t_end]

    # ---- trace -------------------------------------------------------------

    def step_modules(self) -> List[xplane.Event]:
        return xplane.matching(self.trace.modules[0], STEP_MODULE)

    def step_ops(self) -> List[xplane.Event]:
        return xplane.inside(self.trace.ops[0], self.step_modules())

    def kernel_time(self, pattern: str) -> float:
        """Device time of the operations matching ``pattern`` inside
        the serve-step program."""
        return sum(e.dur for e in xplane.matching(self.step_ops(), pattern))

    def busy_s(self) -> float:
        return xplane.busy_s(self.trace)

    def peaks(self) -> Dict[str, float]:
        return costs.peaks(self.device_kind)

    # ---- shapes of the offline cells --------------------------------------

    def fixed_kv_len(self) -> Optional[int]:
        """The one row span of a mix whose requests all have the same
        sizes; None otherwise."""
        p, g = self.mix["prompt_len"], self.mix["gen_len"]
        if p["dist"] != "fixed" or g["dist"] != "fixed":
            return None
        return row_len(self.mix, p["value"], g["value"])

    def live_rows(self) -> int:
        """Rows stepping each step: the closed loop keeps every row full."""
        return self.mix["max_batch"] if self.mix["loop"] == "closed" else 0


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def reader(metrics_dir: str, name: str):
    return load_module(os.path.join(metrics_dir, f"{name}.py"),
                       "metric_" + name.replace(".", "_").replace("-", "_")
                       ).read
