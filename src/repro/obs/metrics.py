"""Sync-free in-jit metrics: device-side accumulation, one flush per run.

The contract: jitted steps compute metrics as plain arrays inside the trace
and return them through their existing aux pytrees. The host side
*records* those device values without looking at them — `MetricsBuffer.record` is just a
list append, adding **zero** device→host syncs to the hot loop — and
converts them all at once at the end of the run with a single
``jax.device_get`` in `MetricsBuffer.flush`. tests/test_obs.py counts
transfers to hold this to "no more than the uninstrumented trainer".
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import numpy as np


def _host_value(v: Any) -> Any:
    a = np.asarray(v)
    if a.ndim == 0:
        return float(a)
    return a.tolist()


class MetricsBuffer:
    """Accumulates per-round device metric pytrees; flushes in one transfer.

    ``record`` keeps device arrays as-is (no sync); ``flush`` performs the
    run's single blocking ``jax.device_get`` over everything recorded and
    returns per-round dicts of host floats (lists for vector metrics)."""

    def __init__(self) -> None:
        self._pending: List[Dict[str, Any]] = []

    def __len__(self) -> int:
        return len(self._pending)

    def record(self, metrics: Dict[str, Any]) -> None:
        self._pending.append(metrics)

    def flush(self) -> List[Dict[str, Any]]:
        if not self._pending:
            return []
        pending, self._pending = self._pending, []
        host = jax.device_get(pending)  # the run's one blocking transfer
        return [{k: _host_value(v) for k, v in m.items()} for m in host]
