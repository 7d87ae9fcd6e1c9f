"""Reduction of a JAX profiler trace to what the per-layer metrics read.

``summarize`` reads the ``.xplane.pb`` a traced run wrote and returns
plain numbers for the window alone: the host span ``bench.window`` that
the harness wraps around the measured chunks.  Device and host events
share the profiler's clock, so every device interval is clipped to that
span: for each device, the union of the intervals in which an operation
ran (busy time), the time of each operation by name, the time of each
compiled program (XLA module) by name, and the idle gaps between
operations; on the host, the spans the benchmark wrapped around its own
calls (``bench.*`` ``TraceAnnotation``s) and the host events that overlap
each idle gap, by which the gaps are labelled.  Each device's
``collective_s`` is the self time of its collective operations
(``COLLECTIVES``, with their ``-start``/``-done`` forms).  Work after the window
(the answers of a serving cell's last requests, the reference) is not
counted.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
# an operation's event is its HLO text, ``%name = shape opcode(...)``:
# the opcode is the first word after the shape that opens a parenthesis
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    """``(name, start, duration)`` events cut to ``[lo, hi)``; those
    wholly outside are dropped."""
    out = []
    for n, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b - a))
    return out


def _self_time(ops) -> dict:
    """Seconds of each operation by name, less the time of operations
    nested inside it (a loop or a conditional holds its body's)."""
    out = defaultdict(float)
    stack = []                      # [end, name, duration, nested]
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            e, n, dd, nested = stack.pop()
            out[n] += (dd - nested) * 1e-9
        if stack:
            stack[-1][3] += d
        stack.append([s + d, name, d, 0.0])
    for e, n, dd, nested in stack:
        out[n] += (dd - nested) * 1e-9
    return dict(out)


def is_collective(op: str) -> bool:
    """Whether an operation's event is a collective's: by its opcode, or,
    where the event is a bare name, by that name less its number."""
    if " = " in op:
        m = _OPCODE.search(" " + op.split(" = ", 1)[1])
        kind = m.group(1) if m else ""
    else:
        kind = op.lstrip("%").split(".")[0]
    for form in ("-start", "-done"):
        kind = kind.removesuffix(form)
    return kind in COLLECTIVES


def _strip(name: str) -> str:
    """A module event's name without its run-specific suffix:
    ``jit_f(123)`` -> ``jit_f``."""
    return name.split("(")[0]


def summarize(planes, *, n_gaps: int = 10) -> dict:
    """``planes``: the ``planes`` of a ``jax.profiler.ProfileData`` (or
    objects with the same ``name``/``lines``/``events`` attributes).
    ``window_s`` is the length of the ``bench.window`` span, and each
    device's numbers count only what ran inside it."""
    devices = []
    host = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(e.name, e.start_ns, e.duration_ns)
                               for e in line.events]
            devices.append((plane.name, ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.duration_ns)
                         for e in line.events if e.duration_ns > 0]

    windows = [(s, d) for n, s, d in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, d = max(windows, key=lambda w: w[1])
    hi = lo + d
    out = {"devices": [], "spans": defaultdict(float),
           "window_s": d * 1e-9}
    for name, dur in ((n, d) for n, _, d in host
                      if n.startswith(SPAN_PREFIX)):
        out["spans"][name] += dur * 1e-9
    for name, ops, modules in devices:
        ops, modules = _clip(ops, lo, hi), _clip(modules, lo, hi)
        busy = _union([[s, s + d] for _, s, d in ops])
        op_time = _self_time(ops)
        module_time = defaultdict(float)
        for n, _, d in modules:
            module_time[_strip(n)] += d * 1e-9
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = sorted(([a, b] for a, b in zip(edges[::2], edges[1::2])
                       if b > a), key=lambda g: g[0] - g[1])[:n_gaps]
        out["devices"].append({
            "name": name,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "op_self_time": op_time,
            "collective_s": sum(t for n, t in op_time.items()
                                if is_collective(n)),
            "module_time": dict(module_time),
            "gaps": [(g[1] - g[0]) * 1e-9 for g in gaps],
            "gap_labels": [_label(g, host) for g in gaps],
        })
    out["spans"] = dict(out["spans"])
    return out


def _label(gap, host) -> str:
    """What the host was doing in an idle gap: the benchmark's span that
    covers most of it, then the longest other host event inside it."""
    s, e = gap
    best_span, best_other = ("", 0), ("", 0)
    for name, hs, hd in host:
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0:
            continue
        if name.startswith(SPAN_PREFIX):
            if ov > best_span[1] or (ov == best_span[1] and
                                     len(name) > len(best_span[0])):
                best_span = (name, ov)
        elif hd <= (e - s) * 4 and ov > best_other[1]:
            best_other = (name, ov)
    parts = [p for p in (best_span[0], best_other[0]) if p]
    return " / ".join(parts) if parts else "(no host event)"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(find_xplane(trace_dir)).planes)
