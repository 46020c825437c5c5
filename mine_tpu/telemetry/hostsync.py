"""Declared host readbacks: the transfer-guard allowlist.

The host-sync sanitizer (mine_tpu/analysis/passes.py) runs hot paths under
`jax.transfer_guard("disallow")`, which rejects every IMPLICIT device
transfer. Some readbacks are intentional — the train loop's log-cadence
`metrics_to_float`, the guard monitor's abort-policy scalars, eval metric
gathers, the serve engine's output fetch — and those call sites declare it:

    with host_readback("train.log_metrics"):
        m = metrics_to_float(metrics)

The declaration does four things: (1) opens a `jax.transfer_guard("allow")`
scope so the sanitizer passes by DECLARATION rather than by path-string
exemption; (2) counts the readback per reason (`readback_counts()`), so a
hot loop syncing more often than its cadence promises is visible; (3) marks
the site for a reader — the string is the documentation; (4) is a
`telemetry.span` named by the reason, so every declared sync has a duration
(`<reason>_ms`) and shows in a profiler trace as `mine.<reason>`.

Host-side and lock-free on the hot path apart from one dict update under a
plain lock; jax is imported lazily so importing telemetry stays stdlib-only
(the package contract).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict

from mine_tpu.telemetry import spans as _spans

_lock = threading.Lock()
_counts: Dict[str, int] = {}


@contextlib.contextmanager
def host_readback(reason: str):
    """Declare an intentional device->host (or host->device) sync. Use the
    dotted-path naming convention of the metrics registry for `reason`."""
    reason = str(reason)
    with _lock:
        _counts[reason] = _counts.get(reason, 0) + 1
    import jax  # lazy: telemetry imports must stay stdlib-only
    with _spans.span(reason), jax.transfer_guard("allow"):
        yield


def readback_counts() -> Dict[str, int]:
    """Per-reason counts of declared readbacks since process start (or the
    last `reset`)."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    """Tests only."""
    with _lock:
        _counts.clear()
