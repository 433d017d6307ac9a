"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

On a TPU each chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules``
holds one event per executable run (``<module>(<fingerprint>)``), its line
``XLA Ops`` one event per operation (named by the HLO instruction,
``%name = type opcode(...)``), and ``Async XLA Ops`` the spans of
asynchronous copies and collectives.  The host plane ``/host:CPU`` holds the
harness's ``jax.profiler.TraceAnnotation`` marks (``bench.<span>``).  All
events share one time base.

* The traced window runs from the first harness mark to the end of the last.
* Busy time of a chip: the union of its operation intervals in the window.
* Step time: the ``XLA Modules`` events of the serve-step modules named at
  set-up; per step, the slowest chip.
* Collective time: the union of a chip's collective operations (all-reduce,
  all-gather, reduce-scatter, all-to-all, collective-permute), and the part
  of it during which no other operation runs on that chip.
* Idle gaps: stretches of the window in which chip 0 runs nothing, divided
  among the harness marks open meanwhile (``other`` where none is: the
  driver admitting requests and asking the batcher).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MARK_PREFIX = "bench."
_COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def subtract(keep: Sequence[Interval], remove: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the (disjoint, sorted) ``keep`` not covered by ``remove``."""
    out = []
    remove = union(remove)
    j = 0
    for a, b in keep:
        while j < len(remove) and remove[j][1] <= a:
            j += 1
        cur, k = a, j
        while k < len(remove) and remove[k][0] < b:
            c, d = remove[k]
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def op_label(name: str) -> str:
    """``%fusion.6 = f32[...] fusion(...)`` -> ``fusion.6``."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or name[:64]


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over the chips
    steps: List[float]                  # device seconds per step, slowest chip
    collective_s: float                 # slowest chip, whole window
    exposed_collective_s: float         # slowest chip, whole window
    ops: Dict[str, float]               # op label -> seconds, all chips
    gaps: List[Tuple[str, float]]       # (mark most over it, seconds), chip 0
    idle_by_mark: Dict[str, float]      # chip 0's idle seconds under each mark

    def step_seconds(self) -> float:
        return sum(self.steps)

    def exposed_collective_ms_per_step(self):
        if not self.steps:
            return None
        return 1e3 * self.exposed_collective_s / len(self.steps)

    def longest_gaps(self, n: int = 10) -> list:
        return [[name, s] for name, s in
                sorted(self.gaps, key=lambda g: -g[1])[:n]]

    def breakdown(self, n: int = 10) -> dict:
        """The top device operations, and chip 0's idle time by what the
        host was doing meanwhile."""
        def top(d):
            return [[k, v] for k, v in sorted(
                d.items(), key=lambda kv: (-kv[1], kv[0]))[:n]]
        return {"device_ops": top(self.ops),
                "idle_gaps": top(self.idle_by_mark)}


def _split(marks, a: float, b: float) -> Dict[str, float]:
    """How [a, b) divides among the harness marks (sorted by start; one
    thread, so disjoint); time under none is ``other``."""
    i = max(0, bisect.bisect_right(marks, (a,), key=lambda m: (m[1],)) - 1)
    out: Dict[str, float] = {}
    while i < len(marks) and marks[i][1] < b:
        name, c, d = marks[i]
        ov = min(b, d) - max(a, c)
        if ov > 0:
            out[name] = out.get(name, 0.0) + ov
        i += 1
    rest = (b - a) - sum(out.values())
    if rest > 0:
        out["other"] = rest
    return out


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def reduce_profile(profile, step_modules, chips: int) -> Reduction:
    """``profile``: a ``jax.profiler.ProfileData``."""
    devices = {}
    marks: List[Tuple[str, float, float]] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            idx = plane.name[len(DEVICE_PREFIX):]
            if idx.isdigit():
                devices[int(idx)] = {ln.name: _events(ln)
                                     for ln in plane.lines}
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                marks += [(n[len(MARK_PREFIX):], a, b)
                          for n, a, b in _events(ln)
                          if n.startswith(MARK_PREFIX)]
    if len(devices) < chips:
        raise ValueError(f"trace has {len(devices)} device planes, the cell "
                         f"uses {chips}")
    if not marks:
        raise ValueError("trace holds no harness marks")
    marks.sort(key=lambda m: m[1])
    lo = min(a for _, a, _ in marks)
    hi = max(b for _, _, b in marks)
    prefixes = tuple(f"{m}(" for m in step_modules)
    busy, coll, exposed, per_chip_steps = [], [], [], []
    ops: Dict[str, float] = {}
    idle0: List[Interval] = []
    for chip in sorted(devices)[:chips]:
        lines = devices[chip]
        op_ev = lines.get("XLA Ops", [])
        ivs = union(clip([(a, b) for _, a, b in op_ev], lo, hi))
        busy.append(total(ivs))
        for n, a, b in op_ev:
            if b > lo and a < hi:
                k = op_label(n)
                ops[k] = ops.get(k, 0.0) + (min(b, hi) - max(a, lo))
        c_ivs = [(a, b) for n, a, b in
                 op_ev + lines.get("Async XLA Ops", [])
                 if _COLLECTIVE.search(n)]
        c_ivs = union(clip(c_ivs, lo, hi))
        compute = [(a, b) for n, a, b in op_ev if not _COLLECTIVE.search(n)]
        coll.append(total(c_ivs))
        exposed.append(total(subtract(c_ivs, clip(compute, lo, hi))))
        per_chip_steps.append([b - a for n, a, b in
                               lines.get("XLA Modules", [])
                               if n.startswith(prefixes)
                               and a >= lo and b <= hi])
        if not idle0:
            idle0 = subtract([(lo, hi)], ivs)
    n_steps = min(len(s) for s in per_chip_steps)
    steps = [max(s[k] for s in per_chip_steps) for k in range(n_steps)]
    gaps, idle_by_mark = [], {}
    for a, b in idle0:
        split = _split(marks, a, b)
        gaps.append((max(split, key=split.get), b - a))
        for name, t in split.items():
            idle_by_mark[name] = idle_by_mark.get(name, 0.0) + t
    return Reduction(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                     steps=steps, collective_s=max(coll),
                     exposed_collective_s=max(exposed), ops=ops, gaps=gaps,
                     idle_by_mark=idle_by_mark)


def reduce(path: str, step_modules, chips: int) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), step_modules, chips)
