"""Reduction of a profiler trace to what the per-layer readers need.

A trace is reduced to plain data: planes, each with named lines of events
``(name, start_ns, duration_ns)``.  ``from_xplane`` reads the profiler's
``.xplane.pb`` with ``jax.profiler.ProfileData``; ``load``/``dump`` keep the
same data as JSON, which is how the tests' recorded traces are committed.

On a TPU the device's operations are the events of the line ``XLA Ops`` of
each plane ``/device:TPU:<i>``, named by their HLO text
(``%sort.11 = (f32[10,112512]...) sort(...)``), which the reduction cuts to
the instruction's name (``sort.11``); the harness's own spans are
``jax.profiler.TraceAnnotation`` events named ``bench/...`` on the host
plane, on the same clock.  A ``while`` loop's event spans the operations
of its body, which have events of their own.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
#: operations whose events enclose other operations' events
CONTROL_FLOW = re.compile(r"^(while|conditional|call)\b")


def op_name(hlo_text: str) -> str:
    """The instruction's name from the HLO text of a device op event."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


@dataclass
class Trace:
    planes: list = field(default_factory=list)  # [{"name", "lines": [...]}]

    def plane(self, name: str):
        for p in self.planes:
            if p["name"] == name:
                return p
        return None

    def device_ops(self) -> dict:
        """``{chip index: [(name, start, dur), ...]}`` from ``XLA Ops``."""
        out = {}
        for p in self.planes:
            m = DEVICE_PLANE.match(p["name"])
            if not m:
                continue
            for line in p["lines"]:
                if line["name"] == OPS_LINE:
                    out[int(m.group(1))] = sorted(
                        (tuple(e) for e in line["events"]), key=lambda e: e[1])
        return out

    def spans(self, name: str | None = None) -> list:
        """Harness spans on the host, ``[(name, start, dur)]``, by start."""
        host = self.plane(HOST_PLANE)
        out = []
        for line in (host or {}).get("lines", []):
            for e in line["events"]:
                if e[0].startswith(SPAN_PREFIX) and (name is None
                                                     or e[0] == name):
                    out.append(tuple(e))
        return sorted(out, key=lambda e: e[1])

    def window(self):
        """(start, end) of the ``bench/window`` span, or None."""
        w = self.spans("bench/window")
        return (w[0][1], w[0][1] + w[0][2]) if w else None


def from_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = []
    for p in pd.planes:
        if not (DEVICE_PLANE.match(p.name) or p.name == HOST_PLANE):
            continue
        lines = []
        for line in p.lines:
            if DEVICE_PLANE.match(p.name) and line.name != OPS_LINE:
                continue
            if p.name == HOST_PLANE:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
            else:
                events = [[op_name(e.name), float(e.start_ns),
                           float(e.duration_ns)] for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": p.name, "lines": lines})
    return Trace(planes)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace(json.load(f)["planes"])


def dump(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump({"planes": trace.planes}, f)


def clip(events, lo: float, hi: float) -> list:
    """Events cut to ``[lo, hi)``, as (name, start, end)."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


def busy_intervals(events, lo: float, hi: float) -> list:
    """The union of the events' intervals inside ``[lo, hi)``."""
    merged = []
    for _, a, b in sorted(clip(events, lo, hi), key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy_intervals(events, lo, hi))


def idle_gaps(events, lo: float, hi: float) -> list:
    """[(start, end)] of the window in which no event runs."""
    gaps, t = [], lo
    for a, b in busy_intervals(events, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def op_time_ns(events, pattern: re.Pattern, lo: float, hi: float) -> float:
    """Device time of the ops whose name ``pattern`` matches at its start."""
    return sum(b - a for name, a, b in clip(events, lo, hi)
               if pattern.match(name))


def host_activity(spans, t: float) -> str:
    """Name of the innermost harness span open at ``t``."""
    best = None
    for name, s, d in spans:
        if s <= t < s + d and name != "bench/window" and (
                best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside any harness span"


def breakdown(trace: Trace, top: int = 10) -> dict | None:
    """The device's ``top`` operations by time (summed over chips and over
    ops of one name; loops, whose events enclose their body's, left out),
    and its ``top`` longest idle gaps named by the host activity at their
    middle, for the first chip."""
    win, ops = trace.window(), trace.device_ops()
    if win is None or not ops:
        return None
    lo, hi = win
    per_op: dict = {}
    for events in ops.values():
        for name, a, b in clip(events, lo, hi):
            if not CONTROL_FLOW.match(name):
                per_op[name] = per_op.get(name, 0.0) + (b - a)
    first = ops[min(ops)]
    spans = trace.spans()
    gaps = sorted(idle_gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[n, ns * 1e-9] for n, ns in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[host_activity(spans, (a + b) / 2), (b - a) * 1e-9]
                      for a, b in gaps],
    }


def trim(trace: Trace, seconds: float) -> Trace:
    """The first ``seconds`` of the window: every event that starts in it,
    with the ``bench/window`` span cut to that length."""
    lo, hi = trace.window()
    hi = min(hi, lo + seconds * 1e9)
    planes = []
    for p in trace.planes:
        lines = []
        for line in p["lines"]:
            ev = [list(e) for e in line["events"]
                  if lo <= e[1] < hi and e[0] != "bench/window"]
            if any(e[0] == "bench/window" for e in line["events"]):
                ev.append(["bench/window", lo, hi - lo])
            if ev:
                lines.append({"name": line["name"], "events": ev})
        planes.append({"name": p["name"], "lines": lines})
    return Trace(planes)
