"""From a profiler trace to device busy time, idle gaps and top operations.

``extract`` reads a trace (``jax.profiler.ProfileData``, as loaded from the
``.xplane.pb`` that ``jax.profiler`` writes) into a plain record: device
operations and the harness's host spans, in ns on the trace's one clock.
``summarize`` reduces such a record. Both are checked with no TPU on a
small trace in the profiler's own format (``bench/tests/data``).

* busy: the union of the intervals in which an operation ran on a device,
  inside the harness's ``bench.window`` span, averaged over the cell's own
  devices (the planes of other chips on the host are left out);
* idle gaps: the complement of that union inside the window, each named by
  the innermost harness span the host was in at the gap's middle;
* device operations: time per operation name inside the window.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
# the line of a TPU device plane that holds one event per executed XLA op
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(log_dir: str):
    """The newest trace ``jax.profiler`` wrote under ``log_dir``."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(find_xplane(log_dir))


def plane_names(devices) -> List[str]:
    """The trace's plane names of JAX ``devices``: ``/device:TPU:<id>``."""
    return [f"/device:{d.platform.upper()}:{d.id}" for d in devices]


def extract(data, planes: Sequence[str]) -> dict:
    """``{"devices": {plane: [[name, start_ns, end_ns], ...]},
    "spans": [[name, start_ns, end_ns], ...]}`` from a ``ProfileData``.

    Only the device planes named in ``planes`` are read, each from its
    ``XLA Ops`` line; a named plane that is missing or has no such line is
    an error."""
    devices: Dict[str, list] = {}
    spans: list = []
    for plane in data.planes:
        if plane.name in planes:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                raise ValueError(f"device plane {plane.name!r} has no "
                                 f"{OPS_LINE!r} line (it has {sorted(lines)})")
            devices[plane.name] = [[e.name, e.start_ns, e.end_ns]
                                   for e in lines[OPS_LINE].events]
        elif plane.name.startswith("/host:"):
            spans += [[e.name, e.start_ns, e.end_ns]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
    missing = sorted(set(planes) - set(devices))
    if missing:
        seen = sorted(p.name for p in data.planes
                      if p.name.startswith("/device:"))
        raise ValueError(f"trace has no plane for {missing} (device planes: "
                         f"{seen})")
    return {"devices": devices, "spans": spans}


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(merged: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi) that no interval of ``merged`` covers."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def name_at(t: float, spans: Sequence[Sequence]) -> str:
    """The innermost (latest-starting) span that holds time ``t``."""
    best, start = WINDOW, float("-inf")
    for name, s, e in spans:
        if s <= t < e and s > start and name != WINDOW:
            best, start = name, s
    return best


def summarize(record: dict, top: int = 10) -> dict:
    """Busy and window seconds, and the ``breakdown`` lists, of a record."""
    windows = [(s, e) for name, s, e in record["spans"] if name == WINDOW]
    if not windows or not record["devices"]:
        raise ValueError("trace holds no bench.window span or no device "
                         "operation")
    lo, hi = windows[0]
    busy, per_op, idle = [], {}, []
    spans = [sp for sp in record["spans"] if sp[0] != WINDOW]
    for ops in record["devices"].values():
        merged = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            if e > lo and s < hi:
                per_op[name] = per_op.get(name, 0.0) + (min(e, hi) - max(s, lo))
        idle += [(e - s, (s + e) / 2) for s, e in gaps(merged, lo, hi)]
    ns = 1e-9
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle_top = sorted(idle, key=lambda g: -g[0])[:top]
    return {"busy_s": sum(busy) / len(busy) * ns,
            "window_s": (hi - lo) * ns,
            "device_ops": [[name, t * ns] for name, t in ops_top],
            "idle_gaps": [[name_at(mid, spans), t * ns]
                          for t, mid in idle_top],
            "op_names": sorted(per_op)}
