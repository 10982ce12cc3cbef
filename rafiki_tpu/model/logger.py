"""In-training structured logging for models.

Parity: SURVEY.md §2 "Model SDK — logger" (upstream ``rafiki/model/log.py``):
``logger.log(...)`` and ``logger.define_plot(...)`` emit structured records
that the TrainWorker persists as TrialLog rows, which the web UI renders as
live charts.

The SDK-facing object is a module-level ``logger`` whose sink is swapped in
by whoever runs the model (TrainWorker → meta store; ``test_model_class`` →
stdout). Models never talk to storage directly.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

LogRecord = Dict[str, Any]
LogSink = Callable[[LogRecord], None]


class ModelLogger:
    """The sink binding is THREAD-LOCAL: in resident-runner mode many
    TrainWorker threads share this module-level logger, and each must
    route its model's records to its own trial row."""

    def __init__(self):
        self._tls = threading.local()

    def set_sink(self, sink: Optional[LogSink]) -> None:
        self._tls.sink = sink

    def current_sink(self) -> Optional[LogSink]:
        """This thread's sink binding. Harnesses that install a
        temporary sink (probes, trial runners) must save this and
        restore it — and usually chain to it — rather than nulling the
        binding on exit."""
        return getattr(self._tls, "sink", None)

    def _emit(self, record: LogRecord) -> None:
        record.setdefault("time", time.time())
        sink = getattr(self._tls, "sink", None)
        if sink is not None:
            sink(record)

    def log(self, msg: str = "", **metrics: Any) -> None:
        """Log a message and/or named metric values at the current instant."""
        record: LogRecord = {"type": "values"}
        if msg:
            record["msg"] = str(msg)
        if metrics:
            record["values"] = {k: _to_py(v) for k, v in metrics.items()}
        self._emit(record)

    def define_plot(self, title: str, metrics: List[str],
                    x_axis: str = "time") -> None:
        """Declare a chart: which logged metrics to plot against which axis."""
        self._emit({"type": "plot", "plot": {
            "title": title, "metrics": list(metrics), "x_axis": x_axis}})

    def define_loss_plot(self) -> None:
        self.define_plot("Loss over epochs", ["loss"], x_axis="epoch")


def _to_py(v: Any) -> Any:
    # numpy / jax scalars → python scalars so records stay JSON-serialisable
    for attr in ("item",):
        if hasattr(v, attr) and getattr(v, "ndim", 1) == 0:
            try:
                return v.item()
            except Exception:
                pass
    return v


logger = ModelLogger()
