"""From a profiler trace to device busy time, kernel time, exposed
collective time and the breakdown.

``Tracer.window()`` wraps the measured window in ``jax.profiler`` with a
host span ``bench.window``; :func:`reduce` reads the ``.xplane.pb`` back
with ``jax.profiler.ProfileData`` and works on plain event lists, so
the arithmetic is checked on a recorded trace.

* busy: the union of the intervals in which an operation ran on a
  device's ``XLA Ops`` line, inside the window, averaged over devices;
* op time: each op's *self* time -- ops nest on that line (a ``while``
  holds its body's ops), so a parent's time excludes its children's;
* kernel time: summed self time of the ops whose HLO text matches the
  kernel's pattern (on the TPU an op's event name is its HLO text);
* exposed collective: collective intervals not covered by any other op
  on the same device;
* idle gaps: the device's idle intervals, each charged to the innermost
  host span on the driving thread at the gap's midpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import gzip
import os
import re
import shutil

import jax

WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce|reducescatter", re.I)


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: int          # ns
    end: int            # ns


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                    # averaged over devices
    n_devices: int
    op_s: dict                       # name -> seconds, summed over devices
    exposed_collective_s: float      # averaged over devices
    collective_s: float              # averaged over devices
    idle_by_host: dict               # host span -> seconds, averaged
    op_n: dict = dataclasses.field(default_factory=dict)
    # name -> number of events, summed over devices

    def kernel_s(self, pattern: str) -> float | None:
        """Seconds of the ops whose HLO text matches ``pattern`` (a
        regular expression), averaged over devices; None when the trace
        holds no such op."""
        rx = re.compile(pattern)
        hits = [s for n, s in self.op_s.items() if rx.search(n)]
        return sum(hits) / self.n_devices if hits else None

    def kernel_n(self, pattern: str) -> int:
        """Events of the ops whose HLO text matches ``pattern``, summed
        over devices."""
        rx = re.compile(pattern)
        return sum(k for n, k in self.op_n.items() if rx.search(n))

    def breakdown(self) -> dict:
        by_label: dict[str, float] = {}
        for n, s in self.op_s.items():
            by_label[label(n)] = by_label.get(label(n), 0.0) + s
        top = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s / self.n_devices] for n, s in top],
                "idle_gaps": [[n, s] for n, s in idle]}


class Tracer:
    """Traces one window into ``log_dir`` (emptied first)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    @contextlib.contextmanager
    def window(self):
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self) -> Reduction:
        files = glob.glob(os.path.join(self.log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no trace under {self.log_dir}")
        return reduce(*events_of(files[0]))


def events_of(path: str):
    """(device op lists, host span list) of one ``.xplane.pb`` (or of a
    gzipped one, ``.xplane.pb.gz``)."""
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name.split(":")[-1]:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([Ev(e.name, int(e.start_ns),
                                       int(e.start_ns + e.duration_ns))
                                    for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [Ev(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                       for e in line.events]
                if any(e.name == WINDOW_SPAN for e in evs):
                    host = evs
    return devices, host


def label(op_text: str, width: int = 120) -> str:
    """A short name for an op: its HLO text up to ``width`` characters
    (the op's id, result shape and kind)."""
    return op_text if len(op_text) <= width else op_text[:width] + "..."


def self_times(evs: list[Ev]) -> list[int]:
    """Each event's duration less its nested children's. An event that
    starts inside another and ends after it overlaps it and is not its
    child."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i].start,
                                                     -evs[i].end))
    own = [e.end - e.start for e in evs]
    stack: list[int] = []
    for i in order:
        e = evs[i]
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= evs[stack[-1]].end:   # nested, not overlapping
            own[stack[-1]] -= e.end - e.start
        stack.append(i)
    return [max(x, 0) for x in own]


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(ivs, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivs if e > lo and s < hi]


def _length(ivs) -> int:
    return sum(e - s for s, e in ivs)


def _subtract(a, b):
    """Parts of union ``a`` not covered by union ``b``."""
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


def reduce(devices: list[list[Ev]], host: list[Ev]) -> Reduction:
    win = [e for e in host if e.name == WINDOW_SPAN]
    if not win or not devices:
        raise ValueError("the trace holds no window span or no device ops")
    lo, hi = win[0].start, win[0].end
    spans = [e for e in host if e.name != WINDOW_SPAN
             and e.end > lo and e.start < hi]
    op_s: dict[str, float] = {}
    op_n: dict[str, int] = {}
    busy = exposed = coll = 0
    idle_by: dict[str, float] = {}
    for ops in devices:
        ops = [Ev(e.name, max(e.start, lo), min(e.end, hi)) for e in ops
               if e.end > lo and e.start < hi]
        for e, own in zip(ops, self_times(ops)):
            op_s[e.name] = op_s.get(e.name, 0.0) + own / 1e9
            op_n[e.name] = op_n.get(e.name, 0) + 1
        all_u = union(_clip([(e.start, e.end) for e in ops], lo, hi))
        busy += _length(all_u)
        c_u = union(_clip([(e.start, e.end) for e in ops
                           if COLLECTIVE.search(e.name)], lo, hi))
        other = union(_clip([(e.start, e.end) for e in ops
                             if not COLLECTIVE.search(e.name)], lo, hi))
        coll += _length(c_u)
        exposed += _length(_subtract(c_u, other))
        for s, e in _subtract([(lo, hi)], all_u):
            mid = (s + e) // 2
            inside = [h for h in spans if h.start <= mid < h.end]
            name = (min(inside, key=lambda h: h.end - h.start).name
                    if inside else "(no host span)")
            idle_by[name] = idle_by.get(name, 0.0) + (e - s) / 1e9
    n = len(devices)
    return Reduction(window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
                     n_devices=n, op_s=op_s, op_n=op_n,
                     exposed_collective_s=exposed / n / 1e9,
                     collective_s=coll / n / 1e9,
                     idle_by_host={k: v / n for k, v in idle_by.items()})
