"""The program's own spans and device scopes in a profiled run, on the clock
of the device trace.

The driver's record holds the trace reduced to the harness's spans
(``trace.load`` with ``context.SPANS``). ``load`` reads the same
``.xplane.pb`` again for what the program adds (``repro.utils.spans``):

- its host spans, each with the stats its keyword arguments became;
- from the ``/host:metadata`` plane, the HLO of every module the window ran,
  whose ``op_name`` metadata maps each instruction to the device scopes it
  was traced under.

The result is the plain format of ``trace.normalize`` with two changes: a
span may carry a fourth element, its stats, and ``"scopes"`` maps a module
name to ``{instruction: (scope, ...)}``, outermost first:

    {"devices": [...], "window": [lo, hi],
     "spans": [[name, start_ns, dur_ns, {stat: value}], ...],
     "scopes": {"jit__compute_body(1702…)": {"fusion.38": ["client_sgd",
                                                           "head"]}}}

Every reduction below reads that dict alone. A program without spans or
scopes (one older than them) reads as None or empty, never as an error.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
import sys
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench.harness import trace as trace_lib
from bench.harness.context import SPANS as HARNESS_SPANS

OUT_DIR = Path(__file__).resolve().parents[2] / ".bench_out"
METADATA_PLANE = "/host:metadata"


# ------------------------------------------------------------------ reading


def program_names():
    """(span names, scope names) of the program, or None for a program
    that has none."""
    try:
        from repro.utils import spans
    except ImportError:
        return None
    return spans.SPANS, spans.SCOPES


def load(record, roots: Sequence[Path] = (OUT_DIR,)) -> Optional[Dict]:
    """The program trace of the run whose record this is: of the traces a
    run leaves under ``<root>/<run>/trace`` (``Context.traced``), the one
    whose ``bench.window`` span is the record's window. None where the run
    was not traced, the program has no spans, or no trace matches. Read
    once per record; the record keeps it for the other readers."""
    if "program_trace" not in record:
        record["program_trace"] = _find(record, roots)
    return record["program_trace"]


def _find(record, roots: Sequence[Path]) -> Optional[Dict]:
    t = record.get("trace")
    names = program_names()
    if t is None or names is None:
        return None
    key = list(t["window"])
    t0 = time.perf_counter()
    wanted = set(HARNESS_SPANS) | set(names[0])
    found = []
    for root in roots:
        found += glob.glob(os.path.join(str(root), "*", "trace", "**",
                                        "*.xplane.pb"), recursive=True)
    for path in sorted(found, key=os.path.getmtime, reverse=True):
        spans = host_spans(path, wanted)
        window = [s for s in spans if s[0] == trace_lib.WINDOW_SPAN]
        if not window or [window[0][1], window[0][1] + window[0][2]] != key:
            continue
        modules = {op[3] for d in t["devices"] for op in d["ops"]}
        out = {"devices": t["devices"], "window": key, "spans": spans,
               "scopes": scope_maps(path, modules, names[1])}
        lines = [f"{len(spans)} spans; HLO read for {len(out['scopes'])} "
                 f"of the {len(modules)} modules the devices ran"]
        lines += notes(out, names[1])
        lines.append(f"read in {time.perf_counter() - t0:.1f} s")
        for line in lines:
            print(f"program trace: {line}", file=sys.stderr)
        return out
    return None


def host_spans(path: str, wanted: Iterable[str]) -> List[List]:
    """The host spans named in ``wanted``, with their stats, sorted."""
    from jax.profiler import ProfileData
    wanted = set(wanted)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted:
                    out.append([e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)])
    out.sort(key=lambda s: s[1])
    return out


def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def proto_fields(b):
    """(field number, value) of a serialized protocol buffer message; a
    length-delimited value comes back as a memoryview of its bytes."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 1:
            value, i = b[i:i + 8], i + 8
        elif kind == 5:
            value, i = b[i:i + 4], i + 4
        elif kind == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protocol buffer wire type {kind}")
        yield key >> 3, value


def hlo_protos(path: str) -> Dict[str, bytes]:
    """Serialized ``HloModuleProto`` of each module in the trace's metadata
    plane, by the module's name as the device plane shows it
    (``jit_f(<program id>)``)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, bytes] = {}
    for field, plane in proto_fields(space):
        if field != 1:                              # XSpace.planes
            continue
        fields = proto_fields(plane)
        name = None
        for num, value in fields:                   # XPlane.name is 2
            if num == 2:
                name = bytes(value).decode()
                break
        if name != METADATA_PLANE:
            continue
        for num, entry in fields:
            if num != 4:                            # XPlane.event_metadata
                continue
            for k, meta in proto_fields(entry):
                if k == 2:
                    _module_of(meta, out)
    return out


def _module_of(meta, out: Dict[str, bytes]) -> None:
    name, blobs = None, []
    for num, value in proto_fields(meta):
        if num == 2:                                # XEventMetadata.name
            name = bytes(value).decode()
        elif num == 5:                              # XEventMetadata.stats
            blobs += [v for k, v in proto_fields(value) if k == 6]
    for blob in blobs:                              # XStat.bytes_value
        for num, value in proto_fields(blob):
            if num == 1 and name:                   # HloProto.hlo_module
                out[name] = bytes(value)


def hlo_text(module_proto: bytes) -> str:
    """The HLO text, metadata included, of a serialized module."""
    from jax._src.lib import xla_client
    return xla_client._xla.HloModule.from_serialized_hlo_module_proto(
        module_proto).to_string()


def scope_maps(path: str, modules: Iterable[str], scopes: Sequence[str]
               ) -> Dict[str, Dict[str, Optional[Tuple[str, ...]]]]:
    """Instruction → scopes of each of ``modules`` (device-plane module
    names) whose HLO the trace holds."""
    protos = hlo_protos(path)
    out = {}
    for module in modules:
        if module in protos:
            out[module] = scope_map(hlo_text(protos[module]), scopes)
    return out


# ------------------------------------------------------------------ scopes

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r"\sfusion\(.*calls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")
_CONTAINER = re.compile(r"(?<![\w\-])(while|conditional|call)\(")


def path_scopes(op_name: str, scopes: Sequence[str]) -> Tuple[str, ...]:
    """The scopes named in an ``op_name`` path, outermost first; a
    transformed scope (``transpose(jvp(head))``) counts as its own."""
    out = []
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in scopes:
            out.append(part)
    return tuple(out)


def scope_map(text: str, scopes: Sequence[str]
              ) -> Dict[str, Optional[Tuple[str, ...]]]:
    """Instruction name → the scopes it was traced under, from the HLO text
    of a compiled module. A fusion takes its root's scopes (failing those,
    its own, then those of the last scoped instruction it holds). A loop,
    conditional or call, whose device event holds the ops it runs, maps to
    None; other instructions under no scope are left out."""
    own: Dict[str, Tuple[str, ...]] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    body: Dict[str, List[str]] = {}
    containers = set()
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
                body[comp] = []
            continue
        is_root, name, rest = m.groups()
        op = _OP_NAME.search(rest)
        own[name] = path_scopes(op.group(1), scopes) if op else ()
        f = _FUSION_CALLS.search(rest)
        if f:
            calls[name] = f.group(1)
        elif _CONTAINER.search(rest.split(" metadata=", 1)[0]):
            containers.add(name)
        if comp is not None:
            body[comp].append(name)
            if is_root:
                roots[comp] = name

    def resolve(name: str, depth: int = 0) -> Tuple[str, ...]:
        called = calls.get(name)
        if called is None or depth > 8:
            return own.get(name, ())
        got = resolve(roots[called], depth + 1) if called in roots else ()
        if got or own.get(name):
            return got or own[name]
        for inner in reversed(body.get(called, [])):
            if own.get(inner):
                return own[inner]
        return ()

    out: Dict[str, Optional[Tuple[str, ...]]] = {}
    for name in own:
        got = None if name in containers else resolve(name)
        if got is None or got:
            out[name] = got
    return out


# ------------------------------------------------------------ reductions


def _in_window(trace: Dict, start: float) -> bool:
    lo, hi = trace["window"]
    return lo <= start <= hi


def spans_named(trace: Dict, name: str) -> List[List]:
    """The spans called ``name`` that start inside the window."""
    return [s for s in trace["spans"]
            if s[0] == name and _in_window(trace, s[1])]


def span_seconds(trace: Dict, name: str) -> List[float]:
    """Durations of the spans called ``name`` in the window, in seconds,
    their children's time included."""
    return [s[2] * 1e-9 for s in spans_named(trace, name)]


def self_seconds(trace: Dict, name: str, children: Sequence[str]
                 ) -> List[float]:
    """For each span called ``name`` in the window: its duration less the
    part of it that spans named in ``children`` cover, in seconds."""
    kids = trace_lib.union((s[1], s[1] + s[2]) for s in trace["spans"]
                           if s[0] in set(children))
    out = []
    for s in spans_named(trace, name):
        covered = trace_lib.length(trace_lib.clip(kids, s[1], s[1] + s[2]))
        out.append((s[2] - covered) * 1e-9)
    return out


def span_stat(trace: Dict, name: str, stat: str) -> List:
    """The value of ``stat`` on each span called ``name`` in the window
    that carries it."""
    return [s[3][stat] for s in spans_named(trace, name)
            if len(s) > 3 and stat in s[3]]


def idle_within(trace: Dict, names: Sequence[str]) -> float:
    """Seconds in which the first device ran no operation while one of the
    spans named in ``names`` was open, inside the window."""
    if not trace["devices"]:
        return 0.0
    lo, hi = trace["window"]
    held = trace_lib.clip(trace_lib.union(
        (s[1], s[1] + s[2]) for s in trace["spans"] if s[0] in set(names)),
        lo, hi)
    if "_busy" not in trace:
        trace["_busy"] = trace_lib.union(
            (o[1], o[1] + o[2]) for o in trace["devices"][0]["ops"])
    return trace_lib.length(trace_lib.subtract(held, trace["_busy"])) * 1e-9


def scope_table(trace: Dict) -> Dict[str, Dict]:
    """Per module: device seconds of its ops in the window (``total``),
    those under some scope (``scoped``), those under each scope at any
    depth (``by_scope``) and those of each unscoped op (``unscoped``), all
    averaged over the devices. Loops and conditionals, whose events hold
    the ops they run, are left out. Computed once per trace."""
    if "_scope_table" in trace:
        return trace["_scope_table"]
    lo, hi = trace["window"]
    acc: Dict[Tuple[str, str], float] = {}
    for dev in trace["devices"]:
        for op in dev["ops"]:
            if lo <= op[1] <= hi:
                key = (op[3], op[0])
                acc[key] = acc.get(key, 0.0) + op[2]
    n = max(len(trace["devices"]), 1)
    table: Dict[str, Dict] = {}
    for (module, label), ns in acc.items():
        name = label.split("[", 1)[0]
        scopes = trace.get("scopes", {}).get(module, {}).get(name, ())
        if scopes is None or trace_lib.CONTAINER.match(name):
            continue
        sec = ns / n * 1e-9
        row = table.setdefault(module, {"total": 0.0, "scoped": 0.0,
                                        "by_scope": {}, "unscoped": {}})
        row["total"] += sec
        if scopes:
            row["scoped"] += sec
            for sc in set(scopes):
                row["by_scope"][sc] = row["by_scope"].get(sc, 0.0) + sec
        else:
            row["unscoped"][label] = row["unscoped"].get(label, 0.0) + sec
    trace["_scope_table"] = table
    return table


def scope_seconds(trace: Dict, scope: str, module: Optional[str] = None
                  ) -> float:
    """Device seconds of the ops traced under ``scope`` (at any depth), in
    modules matching the regular expression ``module``, averaged over the
    devices."""
    rm = re.compile(module) if module else None
    return sum(row["by_scope"].get(scope, 0.0)
               for name, row in scope_table(trace).items()
               if rm is None or rm.search(name))


def has_scopes(trace: Dict) -> bool:
    """Whether any module of the trace maps an instruction to a scope."""
    return any(trace.get("scopes", {}).values())


def notes(trace: Dict, scopes: Sequence[str]) -> List[str]:
    """Lines for the run's log: each scoped module's share of device time
    under a scope, its seconds per scope and the unscoped ops with the most
    time; each span's count, time and the device idle time inside it; and
    the longest idle gaps labelled with the innermost span of the harness
    or the program."""
    out, bare = [], []
    for module, row in sorted(scope_table(trace).items()):
        if row["scoped"] <= 0:
            bare.append(module)
            continue
        per = {sc: round(row["by_scope"].get(sc, 0.0), 6) for sc in scopes}
        top = sorted(row["unscoped"].items(), key=lambda kv: -kv[1])[:10]
        out.append(f"{module}: {row['scoped']:.6f} of {row['total']:.6f} "
                   f"device s under a scope "
                   f"({100 * row['scoped'] / row['total']:.2f}%); {per}; "
                   f"unscoped ops with most time: {top}")
    if bare:
        out.append(f"modules under no scope: {bare}")
    for name in sorted({s[0] for s in trace["spans"]}):
        took = span_seconds(trace, name)
        if took:
            mid, top = statistics.median(took), max(took)
            out.append(f"span {name}: {len(took)} in the window, "
                       f"{sum(took):.6f} s, median {1e3 * mid:.3f} ms, max "
                       f"{1e3 * top:.3f} ms, device idle inside "
                       f"{idle_within(trace, (name,)):.6f} s")
    plain = dict(trace, spans=[s[:3] for s in trace["spans"]])
    out.append(f"idle gaps by span: {trace_lib.idle_gaps(plain)}")
    return out
