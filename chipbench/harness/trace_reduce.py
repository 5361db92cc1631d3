"""From a profiler trace to device busy time, kernel time and the breakdown.

``normalize`` turns an ``.xplane.pb`` into plain lists, which is also the
format of the recorded trace the tests read:

    {"device": [[plane, line, name, start_ns, dur_ns, {"opcode": ...}], ...],
     "host":   [[thread, name, start_ns, dur_ns], ...]}

Device events are the ops of the accelerator planes (``/device:...``);
host events are the benchmark's own annotations (``bench:`` prefix), the
only host events the reduction reads. Both sides share the profiler's
clock.

The reduction measures inside the window (the ``bench:window``
annotation), over the device planes that ran an op in it (one per chip the
cell uses):

- device busy time is, on each plane, the union of its op intervals,
  averaged over the planes;
- an idle gap is an interval of the window in which a plane ran no op; each
  plane's gaps go to the innermost benchmark or program span the host had
  open at the gap's midpoint, and the breakdown divides the sums by the
  number of planes, so its idle time is the window less ``busy_ns``, as the
  average chip sees it (a chip that waits on a collective while the others
  work shows its wait);
- collective time is, on each plane, the union of its collective ops'
  intervals, averaged over the planes;
- a kernel's time is the sum of the durations of the ops whose own name
  names it, over all planes: chip-seconds. Least bytes over one chip's
  bandwidth, divided by chip-seconds, equals least bytes over the planes'
  aggregate bandwidth, divided by the kernel's time per chip.

On one plane, averaging changes nothing.
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict

from chipbench.harness.tracing import HOST_PREFIX, WINDOW_ANNOTATION as WINDOW

# lines of a device plane whose events are single device ops; the other
# lines (modules, steps, launch stats) repeat the same time at a coarser grain
OP_LINES = ("XLA Ops", "Async XLA Ops")
MODULE_LINE = "XLA Modules"  # one event per program execution
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
# the opcodes of the exchanges between chips, each also in its async
# -start/-done form
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
               "all-to-all")
_COLLECTIVE_OPCODES = frozenset(
    c + suffix for c in COLLECTIVES for suffix in ("", "-start", "-done"))


def op_name(hlo: str) -> str:
    """``%nlist_intersect_pallas_es.2 = (f32[..]) custom-call(...)`` ->
    ``nlist_intersect_pallas_es.2``: the op's own name, without the
    operands (which name other ops)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def normalize(xplane_path: str) -> dict:
    """Device events as [plane, line, op name, start, duration, {"opcode":
    ...}] (module events keep their program name); host events: the
    benchmark's annotations."""
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(xplane_path)
    device, host = [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    extra = {}
                    if " = " in ev.name:
                        m = _OPCODE.search(ev.name.split(" = ", 1)[1])
                        extra["opcode"] = m.group(1) if m else ""
                    device.append([plane.name, line.name, op_name(ev.name),
                                   int(ev.start_ns), int(ev.duration_ns), extra])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([line.name, ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"device": device, "host": host}


def _union_ns(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window_ns(trace: dict) -> tuple[int, int]:
    spans = [(s, s + d) for _, name, s, d in trace["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def device_ops(trace: dict) -> list:
    """The device op events (the op lines of every device plane)."""
    return [ev for ev in trace["device"] if ev[1] in OP_LINES]


def _clip(s: int, e: int, w0: int, w1: int):
    s, e = max(s, w0), min(e, w1)
    return (s, e) if e > s else None


def _plane_busy(trace: dict, keep=lambda ev: True) -> dict[str, list]:
    """For each plane that ran an op in the window, the union of the
    intervals of its ops that ``keep`` selects, clipped to the window."""
    w0, w1 = window_ns(trace)
    per_plane: dict[str, list] = defaultdict(list)
    for ev in device_ops(trace):
        iv = _clip(ev[3], ev[3] + ev[4], w0, w1)
        if iv:
            kept = per_plane[ev[0]]  # the plane counts once it ran an op
            if keep(ev):
                kept.append(iv)
    return {plane: _union_ns(iv) for plane, iv in per_plane.items()}


def _mean_over_planes(per_plane: dict[str, list]) -> float:
    if not per_plane:
        return 0.0
    return sum(sum(e - s for s, e in iv) for iv in per_plane.values()) / len(per_plane)


def busy_ns(trace: dict) -> tuple[float, int]:
    """(device busy ns averaged over the planes, window ns)."""
    w0, w1 = window_ns(trace)
    return _mean_over_planes(_plane_busy(trace)), w1 - w0


def collective_ns(trace: dict) -> float:
    """Device time of the collective ops (``COLLECTIVES`` by their recorded
    opcode), in the window, averaged over the planes."""
    return _mean_over_planes(_plane_busy(
        trace, lambda ev: ev[5].get("opcode") in _COLLECTIVE_OPCODES))


def kernel_ns(trace: dict, kernel_names) -> int:
    """Summed device time of the ops whose own name starts with one of
    ``kernel_names`` (a Pallas call's op is named after the function that
    makes it), inside the window, over all planes: chip-seconds."""
    w0, w1 = window_ns(trace)
    total = 0
    for _, _, name, s, d, _ in device_ops(trace):
        if name.startswith(tuple(kernel_names)):
            iv = _clip(s, s + d, w0, w1)
            if iv:
                total += iv[1] - iv[0]
    return total


def idle_gaps(trace: dict) -> dict[str, list[tuple[int, int]]]:
    """For each plane that ran an op in the window, the intervals of the
    window in which it ran none."""
    w0, w1 = window_ns(trace)
    out = {}
    for plane, busy in _plane_busy(trace).items():
        gaps, t = [], w0
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1))
        out[plane] = gaps
    return out


def host_activity(trace: dict, times) -> list[str]:
    """For each time in ``times``, the innermost benchmark or program span
    open then (the shortest one covering it), other than the window."""
    spans = sorted((s, s + d, name) for _, name, s, d in trace["host"]
                   if name != WINDOW)
    order = sorted(range(len(times)), key=lambda i: times[i])
    out = ["unannotated"] * len(times)
    active: list[tuple[int, int, str]] = []  # heap of (end, start, name)
    j = 0
    for i in order:
        t = times[i]
        while j < len(spans) and spans[j][0] <= t:
            heapq.heappush(active, (spans[j][1], spans[j][0], spans[j][2]))
            j += 1
        while active and active[0][0] <= t:
            heapq.heappop(active)
        if active:
            end, start, name = min(active, key=lambda a: a[0] - a[1])
            out[i] = name[len(HOST_PREFIX):]
    return out


def _module_of(trace: dict):
    """op (plane, start) -> the program it ran in, by time containment."""
    mods: dict[str, list] = defaultdict(list)
    for plane, line, name, s, d, _ in trace["device"]:
        if line == MODULE_LINE:
            mods[plane].append((s, s + d, name.split("(", 1)[0]))
    for v in mods.values():
        v.sort()
    starts = {p: [m[0] for m in v] for p, v in mods.items()}

    def find(plane: str, t: int) -> str:
        i = bisect.bisect_right(starts.get(plane, []), t) - 1
        if i >= 0 and mods[plane][i][1] >= t:
            return mods[plane][i][2]
        return "?"
    return find


def breakdown(trace: dict, top: int = 10) -> dict:
    """The contract's breakdown: device ops (``program/op``) by time
    summed over the planes, and idle time by what the host was doing,
    averaged over the planes, each the ``top`` largest, in seconds."""
    w0, w1 = window_ns(trace)
    module = _module_of(trace)
    by_op: dict[str, int] = defaultdict(int)
    for plane, line, name, s, d, _ in device_ops(trace):
        iv = _clip(s, s + d, w0, w1)
        if iv and line == OP_LINES[0]:
            by_op[f"{module(plane, s)}/{name}"] += iv[1] - iv[0]
    by_host: dict[str, int] = defaultdict(int)
    per_plane = idle_gaps(trace)
    for gaps in per_plane.values():
        for (s, e), who in zip(gaps, host_activity(trace, [(s + e) / 2 for s, e in gaps])):
            by_host[who] += e - s
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    planes = len(per_plane)
    return {"device_ops": [[n, v / 1e9] for n, v in ops],
            "idle_gaps": [[n, v / planes / 1e9] for n, v in gaps]}
