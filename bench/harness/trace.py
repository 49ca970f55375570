"""Reduction of a profiler trace to device busy time, kernel time, collective
time and idle gaps labelled by the harness's own host spans.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
dict (``normalize``'s format), which is also what the test fixture holds:

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, dur_ns, module], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "spans": [[name, start_ns, dur_ns], ...],
     "window": [start_ns, end_ns]}

An op's name is its label (``op_label``); a device may also list the
asynchronous collectives in flight under ``"async"``.

Every function below reads that dict alone.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all|\bsend\b|\brecv\b",
                        re.I)

Interval = Tuple[float, float]


# ------------------------------------------------------------------ reading


def xplane_path(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


KERNEL_TARGET = "tpu_custom_call"   # the custom-call target of a Pallas kernel
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
ASYNC_LINE = "Async XLA Ops"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(text: str) -> str:
    """A short, stable label of an op event from its HLO text: the
    instruction's name, and for a custom call its target (a Pallas kernel's
    is ``tpu_custom_call``)."""
    label = text.split(" = ", 1)[0].lstrip("%")
    tgt = _TARGET.search(text)
    return f"{label}[{tgt.group(1)}]" if tgt else label


def load(trace_dir: str, span_names: Iterable[str]) -> Dict:
    """Read the newest trace under ``trace_dir`` into the plain format,
    keeping host spans whose name is in ``span_names``. Writes
    ``layout.json`` beside it: each plane's lines with their event counts,
    extents and first events, and the text of a few custom calls, the shape
    of the trace for a reader of the run."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path(trace_dir))
    wanted = set(span_names)
    devices, spans, layout = [], [], []
    custom: Dict[str, str] = {}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append({
                "line": line.name, "events": len(events),
                "extent_ns": [min(e.start_ns for e in events),
                              max(e.start_ns + e.duration_ns
                                  for e in events)] if events else None,
                "first": [[e.name[:300], e.start_ns, e.duration_ns,
                           {k: str(v)[:200] for k, v in e.stats}]
                          for e in events[:3]]})
        layout.append({"plane": plane.name, "lines": lines})
        if DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": [],
                   "async": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        label = op_label(e.name)
                        if "[" in label and (len(custom) < 24
                                             or KERNEL_TARGET in label):
                            custom.setdefault(label, e.name[:2000])
                        dev["ops"].append([label, e.start_ns, e.duration_ns,
                                           ""])
                elif line.name == MODULES_LINE:
                    dev["modules"].extend([e.name, e.start_ns, e.duration_ns]
                                          for e in line.events)
                elif line.name == ASYNC_LINE:
                    dev["async"].extend(
                        [op_label(e.name), e.start_ns, e.duration_ns]
                        for e in line.events
                        if COLLECTIVE.search(op_label(e.name)))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        spans.append([e.name, e.start_ns, e.duration_ns])
    devices.sort(key=lambda d: d["name"])
    with open(os.path.join(trace_dir, "layout.json"), "w") as f:
        json.dump({"planes": layout, "custom_calls": custom}, f, indent=1)
    return normalize({"devices": devices, "spans": spans})


def normalize(trace: Dict) -> Dict:
    """Fill in module names of ops from the module intervals where the
    trace left them out, sort, and set the window: the harness's
    ``bench.window`` span where there is one, else the extent of all
    events."""
    starts, ends = [], []
    for dev in trace["devices"]:
        dev["ops"].sort(key=lambda o: o[1])
        dev["modules"].sort(key=lambda m: m[1])
        _attribute_modules(dev)
        for o in dev["ops"]:
            starts.append(o[1])
            ends.append(o[1] + o[2])
    for s in trace["spans"]:
        starts.append(s[1])
        ends.append(s[1] + s[2])
    marked = [s for s in trace["spans"] if s[0] == WINDOW_SPAN]
    if marked:
        trace.setdefault("window", [marked[0][1], marked[0][1] + marked[0][2]])
    trace.setdefault("window", [min(starts), max(ends)] if starts
                     else [0.0, 0.0])
    return trace


def _attribute_modules(dev: Dict) -> None:
    mods = dev["modules"]
    j = 0
    for op in dev["ops"]:
        if op[3]:
            continue
        while j < len(mods) and mods[j][1] + mods[j][2] < op[1]:
            j += 1
        if j < len(mods) and mods[j][1] <= op[1]:
            op[3] = mods[j][0]


# ---------------------------------------------------------------- intervals


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` that no interval of
    the (disjoint, sorted) ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _ops(dev: Dict) -> List[Interval]:
    return [(o[1], o[1] + o[2]) for o in dev["ops"]]


# ------------------------------------------------------------ reductions


def window_s(trace: Dict) -> float:
    lo, hi = trace["window"]
    return (hi - lo) * 1e-9


def busy_s(trace: Dict) -> float:
    """Seconds in which some operation ran, per device, averaged over the
    devices."""
    lo, hi = trace["window"]
    if not trace["devices"]:
        return 0.0
    per = [length(clip(union(_ops(d)), lo, hi)) for d in trace["devices"]]
    return sum(per) / len(per) * 1e-9


def op_seconds(trace: Dict, name: Optional[str] = None,
               module: Optional[str] = None) -> Tuple[float, int]:
    """Summed device seconds and count of the op events whose name matches
    the regular expression ``name`` and whose module name matches
    ``module``, over all devices."""
    rn = re.compile(name) if name else None
    rm = re.compile(module) if module else None
    total, count = 0.0, 0
    for dev in trace["devices"]:
        for op in dev["ops"]:
            if rn and not rn.search(op[0]):
                continue
            if rm and not rm.search(op[3]):
                continue
            total += op[2]
            count += 1
    return total * 1e-9, count


def module_seconds(trace: Dict, module: str) -> Tuple[float, int]:
    """Summed device seconds and count of the program (module) events whose
    name matches ``module``, over all devices."""
    rm = re.compile(module)
    total, count = 0.0, 0
    for dev in trace["devices"]:
        for m in dev["modules"]:
            if rm.search(m[0]):
                total += m[2]
                count += 1
    return total * 1e-9, count


def collective_exposed_s(trace: Dict) -> float:
    """Seconds per device, averaged over devices, in which a collective ran
    (as an op, or as an asynchronous collective in flight) and no other
    operation did."""
    if not trace["devices"]:
        return 0.0
    per = []
    for dev in trace["devices"]:
        coll = union([(o[1], o[1] + o[2]) for o in dev["ops"]
                      if COLLECTIVE.search(o[0])]
                     + [(a[1], a[1] + a[2]) for a in dev.get("async", [])])
        comp = union((o[1], o[1] + o[2]) for o in dev["ops"]
                     if not COLLECTIVE.search(o[0]))
        per.append(length(subtract(coll, comp)))
    return sum(per) / len(per) * 1e-9


def top_ops(trace: Dict, n: int = 10) -> List[List]:
    """The ``n`` op names (``module/op``) with the most device seconds,
    summed over devices; loops and conditionals, whose events hold the ops
    they run, are left out."""
    acc: Dict[str, float] = {}
    for dev in trace["devices"]:
        for op in dev["ops"]:
            if CONTAINER.match(op[0]):
                continue
            key = f"{op[3]}/{op[0]}" if op[3] else op[0]
            acc[key] = acc.get(key, 0.0) + op[2] * 1e-9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Dict, n: int = 10) -> List[List]:
    """The ``n`` longest gaps between operations on the first device, each
    labelled with the innermost harness span that holds its middle
    (``"none"`` where none does)."""
    if not trace["devices"]:
        return []
    lo, hi = trace["window"]
    busy = clip(union(_ops(trace["devices"][0])), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        out.append([span_at(trace, (s + e) / 2), (e - s) * 1e-9])
    return out


def span_at(trace: Dict, t: float) -> str:
    """Name of the shortest host span that contains time ``t``."""
    best, best_len = "none", None
    for name, s, d in trace["spans"]:
        if s <= t <= s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best
