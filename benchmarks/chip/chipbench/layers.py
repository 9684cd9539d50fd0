"""The round's layers and the program's own host spans in a profiler trace.

``trace.py`` reduces a trace to the device's operations and the harness's
``bench/*`` spans.  This module keeps, beside that reduction and without
changing it, what the program writes into the same trace:

* **program spans**: the host events of ``repro.obs.trace`` (``exec/*``,
  ``supplier/*``, ``host/gc``), which a recording ``jax.profiler`` session
  receives on the device trace's clock, kept per host line (thread);
* **layer scopes**: the innermost ``jax.named_scope`` segment
  (``fl.grad``, ``fl.local``, ``fl.uplink``, ``fl.server``) of each device
  operation's HLO ``op_name``, kept as ``{op name: scope}`` per chip.  On
  a TPU the op events carry no ``op_name``: it comes from the HLO module
  that the ``/host:metadata`` plane holds for each program, matched by
  the op's ``program_id`` and instruction name.  A fusion carries the
  ``op_name`` XLA gave it.  An operation outside the four keeps its whole
  ``op_name`` (or ``""``), so that it can be named.

From these it reads each layer's device milliseconds per round (every
operation counted once, under its innermost scope), the host milliseconds
per round of the engine's dispatch path, and the device's idle time by
the innermost host span open in it.  ``LayerTrace`` is a ``trace.Trace``,
so every existing reader runs on it unchanged; ``dump``/``load``/``trim``
carry the new fields, and ``trace.load`` still reads a file this module
wrote.
"""
from __future__ import annotations

import json
import re
from dataclasses import dataclass, field

from chipbench import trace as tr

#: host events the program writes (``repro.obs.trace``)
PROGRAM_PREFIXES = ("exec/", "supplier/", "host/")
#: the layers' scopes, as the program names them, and the metric of each
LAYERS = {"fl.grad": "grad", "fl.local": "local_update",
          "fl.uplink": "uplink", "fl.server": "server"}
SCOPE = re.compile(r"\bfl\.(?:grad|local|uplink|server)\b")
METADATA_PLANE = "/host:metadata"
#: the program's spans of the engine's dispatch path, per chunk
DISPATCH_SPANS = ("exec/stack", "exec/dispatch")
OUTSIDE = "outside any span"


def scope_of(op_name: str) -> str:
    """The innermost layer scope in an HLO ``op_name``, else the
    ``op_name`` itself."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else op_name


@dataclass
class LayerTrace(tr.Trace):
    #: {host line name: [[name, start_ns, dur_ns], ...]} of program spans
    program: dict = field(default_factory=dict)
    #: {chip index: {op name: scope}}
    scopes: dict = field(default_factory=dict)
    #: device ns of ops whose name carried two scopes (kept: the larger)
    conflict_ns: float = 0.0

    def program_spans(self, name: str | None = None,
                      line: str | None = None) -> list:
        """Program spans, ``[(name, start, dur)]`` by start, of one host
        line or of all."""
        out = [tuple(e) for ln, evs in self.program.items()
               if line is None or ln == line for e in evs
               if name is None or e[0] == name]
        return sorted(out, key=lambda e: e[1])

    def window_line(self):
        """The host line (thread) that opened ``bench/window``."""
        for line in (self.plane(tr.HOST_PLANE) or {}).get("lines", []):
            if any(e[0] == "bench/window" for e in line["events"]):
                return line["name"]
        return None

    def thread_spans(self) -> list:
        """The harness's and the program's spans on the window's line,
        ``bench/window`` left out."""
        line = self.window_line()
        host = self.plane(tr.HOST_PLANE) or {}
        out = [tuple(e) for ln in host.get("lines", [])
               if ln["name"] == line for e in ln["events"]
               if e[0] != "bench/window"]
        return sorted(out + self.program_spans(line=line),
                      key=lambda e: e[1])


def from_xplane(path: str, base: tr.Trace | None = None) -> LayerTrace:
    """``trace.from_xplane``'s reduction of ``path`` (or ``base``, that
    reduction made already), with the program's spans and the device ops'
    layer scopes."""
    from jax.profiler import ProfileData

    base = tr.from_xplane(path) if base is None else base
    program: dict = {}
    host = ProfileData.from_file(path).find_plane_with_name(tr.HOST_PLANE)
    for line in (host.lines if host is not None else ()):
        evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
               for e in line.events if e.name.startswith(PROGRAM_PREFIXES)]
        if evs:
            program.setdefault(line.name, []).extend(evs)
    with open(path, "rb") as f:
        scopes, conflict = op_scopes(f.read())
    return LayerTrace(base.planes, program, scopes, conflict)


# -- the ops' scopes, from the xplane's protocol buffer ----------------------
#
# ``jax.profiler.ProfileData`` gives neither an op event's ``program_id``
# nor the HLO modules, so the few fields needed are read from the
# ``XSpace`` message directly (tsl/profiler/protobuf/xplane.proto; the HLO
# module from xla/service/hlo.proto): XSpace.planes 1; XPlane name 2,
# lines 3, event_metadata 4 (map: key 1, value 2), stat_metadata 5;
# XEventMetadata name 2, stats 5; XStat metadata_id 1, uint64 3, int64 4,
# bytes 6; XStatMetadata name 2; XLine name 2, events 4; XEvent
# metadata_id 1, duration_ps 3; HloProto hlo_module 1; HloModuleProto
# computations 3; HloComputationProto instructions 2; HloInstructionProto
# name 1, metadata 7; OpMetadata op_name 2.


def _varint(buf, i: int) -> tuple:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf, span=None):
    """``(field number, value)`` of one message in ``buf[span]``: an int
    for a varint, a ``(start, end)`` span for a length-delimited field,
    None for a fixed-width one."""
    i, end = span if span is not None else (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _first(buf, span, number):
    return next((v for f, v in _fields(buf, span) if f == number), None)


def _map_entries(buf, plane, number):
    """``(key, value span)`` of the map field ``number`` of a plane."""
    for f, entry in _fields(buf, plane):
        if f == number:
            yield _first(buf, entry, 1), _first(buf, entry, 2)


def _stat_names(buf, plane) -> dict:
    return {k: _text(buf, _first(buf, v, 2))
            for k, v in _map_entries(buf, plane, 5)}


def _module_op_names(buf, hlo_proto) -> dict:
    """``{instruction name: op_name}`` of a serialized ``HloProto``."""
    out = {}
    module = _first(buf, hlo_proto, 1)
    for f, comp in _fields(buf, module):
        if f != 3:
            continue
        for g, ins in _fields(buf, comp):
            if g != 2:
                continue
            name, op = None, ""
            for h, v in _fields(buf, ins):
                if h == 1:
                    name = _text(buf, v)
                elif h == 7:
                    meta = _first(buf, v, 2)
                    op = _text(buf, meta) if meta else ""
            out[name] = op
    return out


def op_scopes(buf: bytes) -> tuple:
    """``({chip: {op name: scope}}, conflict ns)`` of a serialized xplane:
    each XLA op's scope from its program's HLO module (an op whose
    program is not among the modules: from the modules that hold its
    name, where they agree).  Where one op name
    has two scopes (two programs), the one with more device time wins and
    the other's time is counted in ``conflict ns``."""
    planes = [v for f, v in _fields(buf) if f == 1]
    names = [_text(buf, _first(buf, p, 2)) for p in planes]
    modules = {}  # program id -> {instruction name: op_name}
    for plane, name in zip(planes, names):
        if name != METADATA_PLANE:
            continue
        stat = _stat_names(buf, plane)
        for pid, meta in _map_entries(buf, plane, 4):
            for f, st in _fields(buf, meta):
                if f != 5:
                    continue
                if stat.get(_first(buf, st, 1)) == "Hlo Proto":
                    modules[pid % 2 ** 64] = _module_op_names(
                        buf, _first(buf, st, 6))
    # an op of no known program: the op_name its name has in every module
    # of the trace that holds it, where those agree
    anywhere: dict = {}
    for ops in modules.values():
        for op, path in ops.items():
            anywhere[op] = path if anywhere.get(op, path) == path else ""
    scopes, conflict = {}, 0.0
    for plane, name in zip(planes, names):
        m = tr.DEVICE_PLANE.match(name)
        if not m:
            continue
        stat = _stat_names(buf, plane)
        op_of = {}  # event metadata id -> (op name, scope)
        for mid, meta in _map_entries(buf, plane, 4):
            op = tr.op_name(_text(buf, _first(buf, meta, 2) or (0, 0)))
            pid = None
            for f, st in _fields(buf, meta):
                if f == 5 and stat.get(_first(buf, st, 1)) == "program_id":
                    pid = _first(buf, st, 3)
                    pid = _first(buf, st, 4) if pid is None else pid
            path = modules.get(None if pid is None else pid % 2 ** 64)
            op_of[mid] = (op, scope_of(
                (path if path is not None else anywhere).get(op, "")))
        ns: dict = {}
        for f, line in _fields(buf, plane):
            if f != 3 or _text(buf, _first(buf, line, 2)) != tr.OPS_LINE:
                continue
            for g, ev in _fields(buf, line):
                if g == 4:
                    key = op_of.get(_first(buf, ev, 1))
                    if key is not None:
                        ns[key] = ns.get(key, 0.0) + (
                            _first(buf, ev, 3) or 0) * 1e-3
        best: dict = {}
        for (op, scope), t in sorted(ns.items(), key=lambda kv: -kv[1]):
            if op in best:
                conflict += t
            else:
                best[op] = scope
        scopes[int(m.group(1))] = best
    return scopes, conflict


def load(path: str) -> LayerTrace:
    with open(path) as f:
        doc = json.load(f)
    return LayerTrace(doc["planes"], doc.get("program", {}),
                      {int(k): v for k, v in doc.get("scopes", {}).items()},
                      doc.get("conflict_ns", 0.0))


def dump(trace: LayerTrace, path: str) -> None:
    with open(path, "w") as f:
        json.dump({"planes": trace.planes, "program": trace.program,
                   "scopes": trace.scopes,
                   "conflict_ns": trace.conflict_ns}, f)


def trim(trace: LayerTrace, seconds: float) -> LayerTrace:
    """``trace.trim`` of the planes; the program spans that start in the
    kept window, and the scopes of the ops left."""
    base = tr.trim(trace, seconds)
    lo, hi = base.window()
    program = {ln: [list(e) for e in evs if lo <= e[1] < hi]
               for ln, evs in trace.program.items()}
    kept = base.device_ops()
    scopes = {chip: {n: s for n, s in trace.scopes.get(chip, {}).items()
                     if n in {e[0] for e in kept.get(chip, [])}}
              for chip in trace.scopes}
    return LayerTrace(base.planes, {k: v for k, v in program.items() if v},
                      scopes, trace.conflict_ns)


# -- device time by layer -----------------------------------------------------


def layer_ns(trace: LayerTrace) -> dict:
    """``{chip: {layer or unscoped op name: device ns}}`` in the window:
    each op counted once, under its innermost scope; loops, whose events
    enclose their body's, left out.  Chips without scopes are left out."""
    win, ops = trace.window(), trace.device_ops()
    if win is None:
        return {}
    lo, hi = win
    out = {}
    for chip, events in ops.items():
        scopes = trace.scopes.get(chip)
        if not scopes:
            continue
        acc: dict = {}
        for name, a, b in tr.clip(events, lo, hi):
            if tr.CONTROL_FLOW.match(name):
                continue
            scope = scopes.get(name, "")
            key = LAYERS.get(scope, "unscoped")
            acc[key] = acc.get(key, 0.0) + (b - a)
        out[chip] = acc
    return out


def layer_ms_per_round(trace, layer: str, rounds: int):
    """Device ms per round of ``layer`` (a value of ``LAYERS``) on the chip
    that spends the most on it; None when no op of it ran."""
    if trace is None or not rounds or not isinstance(trace, LayerTrace):
        return None
    ns = max((acc.get(layer, 0.0) for acc in layer_ns(trace).values()),
             default=0.0)
    return ns * 1e-6 / rounds if ns > 0 else None


def unscoped_ops(trace: LayerTrace, top: int = 10) -> list:
    """``[[op name, op_name path, seconds]]`` of the costliest ops outside
    the four layers, summed over chips, in the window."""
    win = trace.window()
    if win is None:
        return []
    lo, hi = win
    per: dict = {}
    for chip, events in trace.device_ops().items():
        scopes = trace.scopes.get(chip, {})
        for name, a, b in tr.clip(events, lo, hi):
            scope = scopes.get(name, "")
            if not tr.CONTROL_FLOW.match(name) and scope not in LAYERS:
                k = (name, scope)
                per[k] = per.get(k, 0.0) + (b - a)
    return [[n, s, ns * 1e-9] for (n, s), ns in
            sorted(per.items(), key=lambda kv: -kv[1])[:top]]


# -- host spans ---------------------------------------------------------------


def dispatch_ms_per_round(trace, rounds: int):
    """Host ms per round in ``exec/stack`` and ``exec/dispatch`` (building
    a chunk's arguments, copying and enqueuing them) inside the window."""
    if (trace is None or not rounds or not isinstance(trace, LayerTrace)
            or trace.window() is None):
        return None
    lo, hi = trace.window()
    spans = [e for e in trace.program_spans(line=trace.window_line())
             if e[0] in DISPATCH_SPANS]
    if not spans:
        return None
    ns = sum(b - a for _, a, b in tr.clip(spans, lo, hi))
    return ns * 1e-6 / rounds


def innermost(spans, lo: float, hi: float) -> list:
    """``[(start, end, name)]``, disjoint and covering ``[lo, hi)``: the
    innermost of the nested ``spans`` open over each piece, or
    ``OUTSIDE``."""
    out, stack, t = [], [], lo

    def emit(upto):
        nonlocal t
        upto = min(max(upto, lo), hi)
        if upto > t:
            out.append((t, upto, stack[-1][0] if stack else OUTSIDE))
            t = upto

    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((name, s + d))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(hi)
    return out


def host_activity(trace: LayerTrace, t: float) -> str:
    """Name of the innermost harness or program span open at ``t`` on the
    window's thread."""
    best = None
    for name, s, d in trace.thread_spans():
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "outside any harness span"


def idle_by_span(trace: LayerTrace, chip: int | None = None) -> dict:
    """``{span name: ns}``: the device's idle time in the window (of
    ``chip``, else the first) split by the innermost span open on the
    window's thread at each instant."""
    win, ops = trace.window(), trace.device_ops()
    if win is None or not ops:
        return {}
    lo, hi = win
    events = ops[min(ops) if chip is None else chip]
    pieces = innermost(trace.thread_spans(), lo, hi)
    out: dict = {}
    i = 0
    for a, b in tr.idle_gaps(events, lo, hi):
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            s, e, name = pieces[j]
            ns = min(e, b) - max(s, a)
            if ns > 0:
                out[name] = out.get(name, 0.0) + ns
            j += 1
    return out


def breakdown(trace: LayerTrace, top: int = 10) -> dict | None:
    """``trace.breakdown`` with the idle gaps named by the innermost span,
    the harness's or the program's, open at their middle."""
    bd = tr.breakdown(trace, top)
    if bd is None:
        return None
    lo, hi = trace.window()
    ops = trace.device_ops()
    gaps = sorted(tr.idle_gaps(ops[min(ops)], lo, hi),
                  key=lambda g: g[0] - g[1])[:top]
    bd["idle_gaps"] = [[host_activity(trace, (a + b) / 2), (b - a) * 1e-9]
                       for a, b in gaps]
    return bd
