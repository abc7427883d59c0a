"""Telemetry records: fresh demand and task readings for a set of apps.

Payload: ``app_ids`` i64[k], ``demand`` f32[k, R], ``tasks`` f32[k].
"""

import numpy as np


def program_event(payload: dict, due_s: float):
    """The record as the program's ``TelemetryDelta``."""
    from repro.service.events import TelemetryDelta

    return TelemetryDelta(app_ids=payload["app_ids"], demand=payload["demand"],
                          tasks=payload["tasks"], collected_at=int(due_s))


def replay(state, payload: dict) -> None:
    """Apply the record to the reference's f64 fleet state."""
    ids = np.asarray(payload["app_ids"], np.int64)
    state.demand[ids] = np.asarray(payload["demand"], np.float64)
    state.tasks[ids] = np.asarray(payload["tasks"], np.float64)
