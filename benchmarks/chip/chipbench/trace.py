"""Reduction of a profiler trace to device busy time, time per module
and per kernel, and the pairing of device steps with the plans the
benchmark recorded.

``load`` turns an ``.xplane.pb`` into plain ``Event`` records; everything
else works on those records, so the tests can hand-build them.  Device
events sit on planes named ``/device:TPU:<n>``: ``XLA Modules`` holds one
event per jitted step (named after the jitted function, ``_decode`` or
``_prefill``), ``XLA Ops`` one per operation, kernels among them under
their own names.  Host spans are the benchmark's
``jax.profiler.TraceAnnotation`` s, all named ``bench.<what>``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
#: the program's jitted step functions and its paged kernel, by name
MODULE_KINDS = (("_decode", "decode"), ("_prefill", "prefill"))
KERNEL = "paged_attention_chunk"


@dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> List[Event]:
    """Device events and the benchmark's host spans of one trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if dev and line.name not in (MODULE_LINE, OPS_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if not dev and not name.startswith("bench."):
                    continue
                stats = {}
                if dev and line.name == OPS_LINE:
                    stats = {k: v for k, v in ev.stats
                             if isinstance(v, (str, int, float))}
                out.append(Event(plane.name, line.name, name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 stats))
    return out


def is_device(ev: Event) -> bool:
    return bool(DEVICE_PLANE.match(ev.plane))


def module_kind(name: str) -> Optional[str]:
    for frag, kind in MODULE_KINDS:
        if frag in name:
            return kind
    return None


def is_kernel(ev: Event) -> bool:
    return KERNEL in ev.name or any(
        isinstance(v, str) and KERNEL in v for v in ev.stats.values())


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """The union of ``[start, end)`` intervals clipped to ``[lo, hi)``,
    as sorted disjoint intervals."""
    spans = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                   if b > lo and a < hi)
    out: List[Tuple[float, float]] = []
    for a, b in spans:
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def span(events: Sequence[Event], name: str) -> Optional[Tuple[float, float]]:
    for ev in events:
        if not is_device(ev) and ev.name == name:
            return ev.start_ns, ev.end_ns
    return None


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Nanoseconds of ``[lo, hi)`` in which some operation ran, averaged
    over the device planes that hold any."""
    planes: Dict[str, list] = {}
    for ev in events:
        if is_device(ev) and ev.line == OPS_LINE:
            planes.setdefault(ev.plane, []).append((ev.start_ns, ev.end_ns))
    if not planes:
        return 0.0
    tot = [sum(b - a for a, b in union(iv, lo, hi)) for iv in planes.values()]
    return sum(tot) / len(tot)


def idle_gaps(events: Sequence[Event], lo: float, hi: float,
              top: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches of ``[lo, hi)`` with no device operation,
    each named by the host span that covers its midpoint (the innermost,
    i.e. latest-starting, of the benchmark's spans), in seconds."""
    ops = [(e.start_ns, e.end_ns) for e in events
           if is_device(e) and e.line == OPS_LINE]
    busy = union(ops, lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    host = [e for e in events if not is_device(e)
            and not e.name.startswith("bench.trace_window")]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        cover = [e for e in host if e.start_ns <= mid < e.end_ns]
        label = max(cover, key=lambda e: e.start_ns).name if cover \
            else "no benchmark span"
        out.append((re.sub(r"\.\d+$", "", label), (b - a) / 1e9))
    return out


def op_name(name: str) -> str:
    """An operation's short name: its HLO instruction name without the
    text of the instruction (``%fusion.12 = bf16[...] ...`` -> ``fusion.12``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_ns(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each device operation with its self time: its duration less the
    time of the operations nested inside it (a loop's event spans its
    body's operations on the same line)."""
    out = []
    by_plane: Dict[str, list] = {}
    for e in events:
        if is_device(e) and e.line == OPS_LINE:
            by_plane.setdefault(e.plane, []).append(e)
    for evs in by_plane.values():
        evs.sort(key=lambda e: (e.start_ns, -e.dur_ns))
        stack: List[list] = []  # [event, child time]
        for e in evs:
            while stack and e.start_ns >= stack[-1][0].end_ns:
                done = stack.pop()
                out.append((done[0], done[0].dur_ns - done[1]))
            if stack:
                stack[-1][1] += e.dur_ns
            stack.append([e, 0.0])
        out.extend((d[0], d[0].dur_ns - d[1]) for d in stack)
    return out


def top_ops(events: Sequence[Event], lo: float, hi: float,
            top: int = 10) -> List[Tuple[str, float]]:
    """Device operations by total self seconds inside ``[lo, hi)``."""
    tot: Dict[str, float] = {}
    for e, ns in self_ns(events):
        if lo <= e.start_ns < hi:
            k = op_name(e.name)
            tot[k] = tot.get(k, 0.0) + ns / 1e9
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def pair_steps(events: Sequence[Event], plans: Dict[int, object],
               lo: float, hi: float) -> List[Tuple[object, Event]]:
    """Pair each recorded plan whose host span (``bench.plan.<i>``)
    starts in ``[lo, hi)`` with the device module event that ran it: the
    first unpaired module of the plan's kind that starts after the span
    does and ends before ``hi``.  Plans are the benchmark's own records,
    keyed by ``i``; each has a ``kind`` of ``decode`` or ``prefill``
    (mixed plans run the ``_prefill`` module)."""
    spans = sorted((e.start_ns, int(e.name.rsplit(".", 1)[1]))
                   for e in events if not is_device(e)
                   and e.name.startswith("bench.plan.")
                   and lo <= e.start_ns < hi)
    mods = sorted((e for e in events if is_device(e)
                   and e.line == MODULE_LINE and module_kind(e.name)
                   and e.start_ns >= lo and e.end_ns <= hi),
                  key=lambda e: e.start_ns)
    used = [False] * len(mods)
    out, j0 = [], 0
    for start, i in spans:
        plan = plans.get(i)
        if plan is None:
            continue
        want = "decode" if plan.kind == "decode" else "prefill"
        while j0 < len(mods) and (used[j0] or mods[j0].start_ns < start):
            j0 += 1
        for j in range(j0, len(mods)):
            if not used[j] and module_kind(mods[j].name) == want:
                used[j] = True
                out.append((plan, mods[j]))
                break
    return out


def kernel_ns_within(events: Sequence[Event], mods: Sequence[Event]) -> float:
    """Device nanoseconds of kernel events inside the given modules."""
    iv = sorted((m.start_ns, m.end_ns, m.plane) for m in mods)
    tot = 0.0
    kern = sorted((e for e in events if is_device(e) and e.line == OPS_LINE
                   and is_kernel(e)), key=lambda e: e.start_ns)
    import bisect

    starts = [a for a, _, _ in iv]
    for e in kern:
        k = bisect.bisect_right(starts, e.start_ns) - 1
        if k >= 0 and e.end_ns <= iv[k][1] and e.plane == iv[k][2]:
            tot += e.dur_ns
    return tot
