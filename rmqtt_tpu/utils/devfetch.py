"""Timeout-guarded device→host fetches.

``np.asarray`` of a device array blocks until the device has produced it.
If the device never does — a hung kernel, a lost chip — the call blocks
forever and takes the whole process with it, results already measured
included. When ``RMQTT_FETCH_TIMEOUT`` (seconds) is set, fetches run on a
daemon worker thread and raise ``TimeoutError`` instead of hanging, so a
caller (a bench's per-config guard, the routing service) can record the
failure and continue or exit. Unset — the default everywhere: the first
chip run (``chip_smoke.py``, PR 21) completed every fetch, so nothing arms
it on a healthy chip — it is a plain ``np.asarray``: no thread, no
overhead.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

import numpy as np

_timeout: Optional[float] = None
_loaded = False


def fetch_timeout() -> Optional[float]:
    global _timeout, _loaded
    if not _loaded:
        raw = os.environ.get("RMQTT_FETCH_TIMEOUT", "")
        _timeout = float(raw) if raw else None
        _loaded = True
    return _timeout


def set_fetch_timeout(seconds: Optional[float]) -> None:
    global _timeout, _loaded
    _timeout = seconds
    _loaded = True


def fetch(arr, what: str = "device fetch") -> np.ndarray:
    """``np.asarray(arr)`` under the configured deadline."""
    t = fetch_timeout()
    if t is None:
        return np.asarray(arr)
    box: dict = {}

    def run() -> None:
        try:
            box["v"] = np.asarray(arr)
        except BaseException as e:  # surfaced on the caller thread
            box["e"] = e

    th = threading.Thread(target=run, daemon=True, name="devfetch")
    th.start()
    th.join(t)
    if "v" in box:
        return box["v"]
    if "e" in box:
        raise box["e"]
    # the worker stays parked on the fetch; daemon=True means it cannot
    # block process exit
    raise TimeoutError(f"{what} exceeded {t:.0f}s (wedged accelerator?)")
