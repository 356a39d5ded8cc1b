"""What the readers of the program's own spans share.

The program records its spans with ``repro.obs``: per span path, the count
and the total and self seconds, in the thread that calls it. A program
without that module has no recorder, and every reader then returns None.
The dedup readers take paths under ``dedup.add_batch``: set-up signs
without ``add_batch``, so those paths hold window time only.
"""
from __future__ import annotations

import importlib


def span(path: str):
    """``repro.obs.totals()``'s entry for ``path``: ``{"count", "total_s",
    "self_s"}``; None where the program has no recorder or never closed a
    span at ``path``."""
    try:
        obs = importlib.import_module("repro.obs")
    except ImportError:
        return None
    return obs.totals()["spans"].get(path)


def window_share(path: str, facts: dict):
    """The total seconds of ``path`` as a percentage of the window."""
    s = span(path)
    if s is None or not facts.get("window_s"):
        return None
    return 100.0 * s["total_s"] / facts["window_s"]
