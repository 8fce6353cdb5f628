"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Ops`` line holds
one event per executed operation (its text starts ``%<op> = ...``) and the
``XLA Modules`` line one event per executed program (``jit_<fn>(<hash>)``);
an operation belongs to the program whose event contains it. Host spans
are on the ``/host:CPU`` plane: the window is the harness's
``chipbench.window`` span, and device idle time inside it is attributed to
the innermost host span that covers the middle of each idle gap.
"""

from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
_MODULE = re.compile(r"^(.*?)(\(\d+\))?$")
_OP = re.compile(r"^%?([^ =]+)")
# host spans that name what the host was doing; anything else in the loop
# counts as the serve loop's own Python
_HOST_LABELS = {
    "PjitFunction(serve_step)": "host:decode_step",
    "PjitFunction(prefill)": "host:prefill",
    "PjitFunction(prefill_tail)": "host:prefill_tail",
    "PjitFunction(paged_scatter)": "host:scatter",
    "PjitFunction(paged_prefix_view)": "host:prefix_view",
    "np.asarray(jax.Array)": "host:device_to_host",
}

Interval = Tuple[float, float]


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _self_times(ops):
    """(start, end, name, self ns) per op. Ops nest on their line (a
    ``while`` holds the ops of its body); an op's self time leaves out the
    time of the ops inside it, so op totals add up to busy time."""
    out = []
    stack: List[list] = []
    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= a:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][3] -= min(b, stack[-1][1]) - a
        stack.append([a, b, name, b - a])
    out.extend(tuple(s) for s in reversed(stack))
    return out


def module_name(event_name: str) -> str:
    return _MODULE.match(event_name).group(1)


def op_name(event_name: str) -> str:
    return _OP.match(event_name).group(1)


def _host_label(name: str) -> Optional[str]:
    if name.startswith("host:"):
        return name
    if name in _HOST_LABELS:
        return _HOST_LABELS[name]
    if name.startswith("PjitFunction("):
        return "host:dispatch"
    return None


def reduce(path: str, top: int = 10) -> Dict[str, object]:
    """Summary of one trace file:

    ``window_s``    length of the window span (the whole device activity
                    when the span is missing);
    ``busy_s``      union of the intervals in which an operation ran,
                    averaged over the devices that ran any;
    ``modules``     program name -> [device seconds, executions];
    ``ops``         "<program>/<op>" -> device seconds (all ops);
    ``device_ops``  the ``top`` ops by device seconds;
    ``idle_gaps``   host label -> idle device seconds, the ``top`` largest.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Interval] = None
    host: List[Tuple[float, float, str]] = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                           module_name(e.name))
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
            ops = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name))
                   for e in (lines["XLA Ops"].events
                             if "XLA Ops" in lines else ())]
            devices.append((mods, ops))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    label = _host_label(e.name)
                    if label is not None:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     label))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    if window is None:
        starts = [o[0] for _, ops in devices for o in ops]
        ends = [o[1] for _, ops in devices for o in ops]
        window = (min(starts), max(ends))
    w0, w1 = window

    modules: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    ops_s: Dict[str, float] = collections.defaultdict(float)
    busy = []
    idle_iv: List[Interval] = []
    for d, (mods, ops) in enumerate(devices):
        for a, b, name in mods:
            if a >= w0 and b <= w1:
                modules[name][0] += (b - a) * 1e-9
                modules[name][1] += 1
        starts = [m[0] for m in mods]
        iv = []
        for a, b, name, self_ns in _self_times(ops):
            if b <= w0 or a >= w1:
                continue
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= a else "?"
            ops_s[f"{prog}/{name}"] += self_ns * 1e-9
            iv.append((max(a, w0), min(b, w1)))
        if not iv:
            continue
        u = _union(iv)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        if d == 0:
            edges = [w0] + [x for ab in u for x in ab] + [w1]
            idle_iv = [(edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2)
                       if edges[i + 1] > edges[i]]

    host.sort()
    idle: Dict[str, float] = collections.defaultdict(float)
    active: List[Tuple[float, float, str]] = []
    j = 0
    for a, b in idle_iv:          # sorted, so the sweep never steps back
        mid = 0.5 * (a + b)
        while j < len(host) and host[j][0] <= mid:
            active.append(host[j])
            j += 1
        active = [h for h in active if h[1] >= mid]
        label = min(active, key=lambda h: h[1] - h[0])[2] if active \
            else "host:serve_loop"
        idle[label] += (b - a) * 1e-9

    by_op = sorted(ops_s.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "modules": {k: list(v) for k, v in modules.items()},
        "ops": dict(ops_s),
        "device_ops": [[k, v] for k, v in by_op[:top]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }
