"""What the program's own spans and scopes put in a traced run.

The program opens host spans named ``repro:<name>`` (``repro.obs.spans``)
whose counts are stats of the same event, and names the device operations
of its compiled loop with ``jax.named_scope`` (``SCOPES``). A TPU's
``XLA Ops`` events carry only the HLO instruction (``%fusion.12 = ...``);
the scope is in that instruction's ``op_name`` metadata, which the
profiler keeps in the HLO proto of each program on the ``/host:metadata``
plane. ``load()`` reads the newest ``*.xplane.pb`` under ``.bench_traces/``
(the traced run has just written it: a reader's context does not carry its
path) and keeps, once per file:

* ``spans`` — ``(name, start_ns, end_ns, stats)`` of every ``repro:`` span,
  the prefix dropped;
* ``ops`` — ``(instruction, scope, start_ns, end_ns)`` of each outermost
  operation on device 0's ``XLA Ops`` line. Operations nested in it (the
  body of an inner while loop) fall inside it and under its scope.
  ``scope`` is the innermost of ``SCOPES`` in the instruction's
  ``op_name``, or in its fused computation's root's where it has none;
  else None;
* ``modules`` — ``(name, start_ns, end_ns)`` on device 0's ``XLA Modules``
  line;
* ``dropped`` — ``(start_ns, end_ns)`` of device 0's ``Trace Buffers
  Dropped`` events: where the profiler threw the device's events away to
  keep the file under its size limit, so no idle can be read there;
* ``window`` — the harness's ``bench:window`` span, else the extent of the
  ``repro:`` spans, else None.

Times are on the profiler's one clock, host and device alike. With no
``repro:`` span or scope (a program without them) the readers find nothing
and return None.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Tuple

from .trace_reduce import _union as union

TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_traces"
SPAN_PREFIX = "repro:"
WINDOW_SPAN = "bench:window"
DEVICE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TRACEME_LINE = "XLA TraceMe"
DROPPED = "Trace Buffers Dropped"
METADATA_PLANE = "/host:metadata"
SCOPES = ("cycle.arbiter", "cycle.patterns", "cycle.recode",
          "cycle.dynamic", "loop.quiescence")
QUIESCENCE = "loop.quiescence"
HLO_PROTO_STAT = "Hlo Proto"
# below these recorded shares of the call a truncated trace no longer
# stands for it: the scope readers and idle_in_wait.memsys read None
MIN_TRIP_COVERAGE = 0.10
MIN_RECORDED_WAIT = 0.10
_SCOPE_RE = re.compile(
    r"(?:^|/)(" + "|".join(re.escape(s) for s in SCOPES) + r")(?=/|$)")

_CACHE: Dict[Tuple[str, float], dict] = {}


def newest(trace_dir: Optional[Path] = None) -> Optional[str]:
    files = glob.glob(os.path.join(str(trace_dir or TRACE_DIR), "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: Optional[str] = None) -> Optional[dict]:
    """The parsed trace of ``path`` (default: the newest under
    ``.bench_traces/``), or None where there is none."""
    path = path or newest()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        from jax.profiler import ProfileData

        _CACHE.clear()
        _CACHE[key] = parse(ProfileData.from_file(path).planes,
                            hlo_scopes(path))
    return _CACHE[key]


# ------------------------------------------------- HLO protos in the trace
def _fields(buf: bytes, lo: int = 0, hi: Optional[int] = None
            ) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a protobuf message in ``buf[lo:hi]``;
    a length-delimited value is its ``(start, end)`` in ``buf``."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {kind}")
        yield key >> 3, value


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _scope_of(op_name: str) -> Optional[str]:
    hits = _SCOPE_RE.findall(op_name)
    return hits[-1] if hits else None


def _module_scopes(buf: bytes, module: Tuple[int, int]) -> Dict[str, str]:
    """``{instruction: scope}`` of one ``HloModuleProto``: computations (3)
    of instructions (2) with name (1), metadata (7) holding op_name (2),
    id (35), operand ids (36) and called computation ids (38); a
    computation's id (5) and root id (6)."""
    instrs, roots = {}, {}
    for f, comp in _fields(buf, *module):
        if f != 3:
            continue
        cid = root = None
        for g, v in _fields(buf, *comp):
            if g == 2:
                rec = {"operands": [], "calls": [], "scope": None}
                for h, w in _fields(buf, *v):
                    if h == 1:
                        rec["name"] = buf[w[0]:w[1]].decode()
                    elif h == 7:
                        for k, x in _fields(buf, *w):
                            if k == 2:
                                rec["scope"] = _scope_of(
                                    buf[x[0]:x[1]].decode())
                    elif h == 35:
                        rec["id"] = w
                    elif h == 36:
                        rec["operands"].append(w)
                    elif h == 38:
                        rec["calls"].append(w)
                instrs[rec.get("id")] = rec
            elif g == 5:
                cid = v
            elif g == 6:
                root = v
        roots[cid] = root

    def scope(rec):
        if rec["scope"]:
            return rec["scope"]
        for c in rec["calls"]:     # a fusion: its root's, else the root's
            root = instrs.get(roots.get(c))     # operands' (a tuple)
            for r in ([root] + [instrs.get(o) for o in root["operands"]]
                      if root else []):
                if r and r["scope"]:
                    return r["scope"]
        return None

    return {r["name"]: s for r in instrs.values()
            if "name" in r and (s := scope(r))}


def _map_values(buf: bytes, entries) -> Iterator[Tuple[int, int]]:
    """The values (2) of a protobuf map's entries."""
    for entry in entries:
        for h, v in _fields(buf, *entry):
            if h == 2:
                yield v


def hlo_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """``{program name: {instruction: scope}}`` from the HLO protos on the
    trace's ``/host:metadata`` plane. XSpace planes (1); a plane's name
    (2), event metadata (4) and stat metadata (5), map values of which an
    event's holds the program's name (2) and stats (5), a stat's its name
    (2). The stat named ``Hlo Proto`` (metadata id (1)) holds in its bytes
    (6) an ``HloProto``, module (1). A file this decoder misreads raises."""
    with open(path, "rb") as f:
        buf = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        parts = list(_fields(buf, *plane))
        names = [buf[v[0]:v[1]].decode() for g, v in parts if g == 2]
        if names != [METADATA_PLANE]:
            continue
        proto_ids = set()
        for meta in _map_values(buf, [v for g, v in parts if g == 5]):
            fields = dict(_fields(buf, *meta))
            name = fields.get(2)
            if name and buf[name[0]:name[1]] == HLO_PROTO_STAT.encode():
                proto_ids.add(fields.get(1, 0))
        for meta in _map_values(buf, [v for g, v in parts if g == 4]):
            name, protos = None, []
            for k, v in _fields(buf, *meta):
                if k == 2:
                    name = buf[v[0]:v[1]].decode()
                elif k == 5:
                    stat = list(_fields(buf, *v))
                    if dict(stat).get(1, 0) in proto_ids:
                        protos += [w for m, w in stat if m == 6]
            for proto in protos:
                for m, module in _fields(buf, *proto):
                    if m == 1 and name:
                        out[name] = _module_scopes(buf, module)
    return out


# ------------------------------------------------------------------- parse
def _instruction(event_name: str) -> str:
    return event_name.split(" ", 1)[0].lstrip("%")


def _base(program: str) -> str:
    """A program's name without its ``(id)``."""
    return program.split("(", 1)[0]


def _outermost(events) -> List[Tuple[str, int, int]]:
    """``(name, start, end)`` of the events that no other event holds,
    in start order (a holding event before those it holds)."""
    out: List[Tuple[str, int, int]] = []
    top_end = None
    for s, neg, name in sorted((e.start_ns, -e.duration_ns, e.name)
                               for e in events):
        if top_end is None or s >= top_end:
            top_end = s - neg
            out.append((name, s, top_end))
    return out


def parse(planes, programs: Dict[str, Dict[str, str]]) -> dict:
    """The spans, device-0 ops, modules and dropped stretches of
    ``ProfileData.planes`` (or anything with the same ``name``/``lines``/
    ``events``/``stats``), ops scoped through ``programs``
    (``hlo_scopes``; a program is matched by its name, else by its name
    without the id where only one program has it)."""
    spans, window, modules, dropped, raw = [], [], [], [], []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns, end, dict(e.stats)))
                    elif e.name == WINDOW_SPAN:
                        window.append((e.start_ns, end))
        elif plane.name == DEVICE:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    raw = _outermost(line.events)
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns,
                                e.start_ns + e.duration_ns)
                               for e in line.events]
                elif line.name == TRACEME_LINE:
                    dropped += [(e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events if e.name == DROPPED]
    spans.sort(key=lambda s: s[1])
    modules.sort(key=lambda m: m[1])
    bases = collections.Counter(_base(p) for p in programs)
    starts = [m[1] for m in modules]
    ops, memo = [], {}
    for name, s, e in raw:
        k = bisect.bisect_right(starts, s) - 1
        module = modules[k][0] if k >= 0 and s < modules[k][2] else None
        if (module, name) not in memo:
            table = programs.get(module)
            if table is None and module and bases[_base(module)] == 1:
                table = next(t for p, t in programs.items()
                             if _base(p) == _base(module))
            instr = _instruction(name)
            memo[module, name] = (instr, (table or {}).get(instr))
        ops.append(memo[module, name] + (s, e))
    if window:
        win = (min(s for s, _ in window), max(e for _, e in window))
    elif spans:
        win = (spans[0][1], max(s[2] for s in spans))
    else:
        win = None
    return {"spans": spans, "ops": ops, "modules": modules,
            "dropped": union(dropped), "window": win}


# ------------------------------------------------------------- quantities
def overlap(a, b) -> int:
    """Length of the intersection of two lists of disjoint intervals."""
    return sum(max(0, min(e1, e2) - max(s1, s2))
               for s1, e1 in a for s2, e2 in b)


def in_window(t: dict, start: int, end: int) -> bool:
    lo, hi = t["window"]
    return start < hi and end > lo


def sweep_spans(t: Optional[dict], name: Optional[str] = None) -> list:
    """The traced call's ``sweep.*`` spans (only ``name`` if given)."""
    if t is None or t["window"] is None:
        return []
    return [s for s in t["spans"] if s[0].startswith("sweep.")
            and (name is None or s[0] == name) and in_window(t, s[1], s[2])]


def span_self_ns(spans) -> List[int]:
    """Each span's length less the spans nested directly inside it."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1],
                                                     -spans[i][2]))
    own = [e - s for _, s, e, _ in spans]
    stack: List[int] = []
    for i in order:
        _, s, e, _ = spans[i]
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def recorded_trips(t: dict) -> int:
    """While-loop trips the device trace recorded in the window: the runs
    of the loop condition's most frequent operation (one a trip, and one
    more where the loop's exit was recorded)."""
    runs = collections.Counter(i for i, sc, s, e in t["ops"]
                               if sc == QUIESCENCE and in_window(t, s, e))
    return max(runs.values(), default=0)


def trip_coverage(t: Optional[dict]) -> Optional[float]:
    """Share of the call's loop-condition runs that the trace recorded:
    ``recorded_trips`` over the ``trips`` of each ``sweep.summarize``,
    plus the one exit run a batch. Under 1 where the profiler dropped
    events; None where no ``sweep.summarize`` span counts its trips."""
    counts = [st["trips"] for _, _, _, st in sweep_spans(t, "sweep.summarize")
              if "trips" in st]
    if not counts:
        return None
    return recorded_trips(t) / (sum(counts) + len(counts))


def scope_trip_us(t: Optional[dict], scope: str) -> Optional[float]:
    """Device-0 time of the ops under ``scope`` in the window, per trip
    the trace recorded (microseconds); None where the trace recorded
    under ``MIN_TRIP_COVERAGE`` of the call's trips."""
    cover = trip_coverage(t)
    if cover is None or cover < MIN_TRIP_COVERAGE:
        return None
    n = recorded_trips(t)
    ns = sum(e - s for i, sc, s, e in t["ops"]
             if sc == scope and in_window(t, s, e))
    return ns / n * 1e-3 if n and ns else None
