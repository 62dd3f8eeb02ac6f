"""Device self-time of the training step by the program's own names.

The program names each part of its step with ``jax.named_scope``
(``repro.profile.scopes``); the compiler keeps the name stack as each
op's ``op_name``, and the profiler stores it as the ``tf_op`` stat of
the op's event metadata, e.g.
``jit(train_step)/transpose(jvp(model))/while/body/closed_call/
checkpoint/rematted_computation/attention/...``. ``ProfileData`` does
not expose metadata stats, so :func:`read_xspace` decodes the few
fields needed from the ``.xplane.pb`` itself (protobuf wire format; no
dependency).

:func:`reduce` keeps the ops of the step's module (``jit_train_step``,
matched by the ``program_id`` stat) on each device's ``XLA Ops`` line,
clipped to the ``bench.window`` span, takes each op's self time
(:func:`harness.trace.self_times`) and sorts it:

* direction: ``recompute`` (``rematted_computation``, the backward's
  re-run of a ``jax.checkpoint`` body), ``backward``
  (``transpose(...)``), ``forward`` (``jvp(...)``), else ``outside``
  autodiff;
* scope path: the program scopes in the name stack, outermost first,
  under the step part they belong to (``model/attention``,
  ``optimizer/sr_bits``).

Ops with no ``tf_op`` (copies and relayouts the compiler inserts) are
``(no name)``. Named ops under no program scope are ``(unscoped)``;
they may hold at most ``UNSCOPED_LIMIT`` of the step's busy time, or
the reduction raises: a scope that drops out of the program fails the
run instead of moving its time elsewhere. A program that names no scope
at all (one older than the names) reduces to None.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import sys

from harness.spec import BENCH_DIR
from harness.trace import WINDOW_SPAN, Ev, self_times, union

OUT_DIR = BENCH_DIR / "out"
TRACE_DIR = OUT_DIR / "trace"                # where run.py's Tracer writes
STEP_MODULE = "jit_train_step"
_MODULE = re.compile(re.escape(STEP_MODULE) + r"\((-?\d+)\)")
_U64 = 1 << 64

# the program's names (repro.profile.scopes), each under its step part
STEP_PARTS = ("model", "transport", "optimizer", "step_metrics")
PART_OF = {"embed": "model", "attention": "model", "mlp": "model",
           "norm": "model", "head": "model", "loss": "model",
           "sr_bits": "optimizer", "layout": "optimizer",
           "fused_adamw": "optimizer", "fused_sgd": "optimizer"}
KNOWN = frozenset(STEP_PARTS) | frozenset(PART_OF)

FORWARD, BACKWARD, RECOMPUTE, OUTSIDE = ("forward", "backward",
                                         "recompute", "outside")
NO_NAME, UNSCOPED = "(no name)", "(unscoped)"
UNSCOPED_LIMIT = 0.01
TOP = 20
_WRAPPER = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
_STATS = ("tf_op", "program_id")     # the event metadata stats read


# -- the .xplane.pb, read directly --------------------------------------

def _varint(b: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, i: int = 0, end: int | None = None):
    """(field number, value) of one message: an int for a varint or a
    fixed-width field, a (start, end) slice for a length-delimited one."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = int.from_bytes(b[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(b[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield num, v


def _str(b: bytes, sl) -> str:
    return b[sl[0]:sl[1]].decode("utf-8", "replace")


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict             # line name -> [(metadata id, start ns, end ns)]
    names: dict             # event metadata id -> name
    stats: dict             # event metadata id -> {stat name: value}


# Field numbers (tsl/profiler/protobuf/xplane.proto): XPlane 2 name, 3
# lines, 4 event_metadata, 5 stat_metadata; XLine 2 name, 3 timestamp_ns,
# 4 events; XEvent 1 metadata_id, 2 offset_ps, 3 duration_ps; the
# metadata 1 id, 2 name, 5 stats; XStat 1 metadata_id, 3 uint64, 4 int64,
# 5 str, 7 ref.
def _plane(b: bytes, s: int, e: int) -> Plane:
    name, lines, names, raw_stats, stat_names = "", {}, {}, {}, {}
    for num, v in _fields(b, s, e):
        if num == 2:
            name = _str(b, v)
        elif num == 3:                                  # XLine
            lname, ts, evs = "", 0, []
            for ln, lv in _fields(b, *v):
                if ln == 2:
                    lname = _str(b, lv)
                elif ln == 3:
                    ts = lv
                elif ln == 4:                           # XEvent
                    mid = off = dur = 0
                    for en, ev in _fields(b, *lv):
                        if en == 1:
                            mid = ev
                        elif en == 2:
                            off = ev
                        elif en == 3:
                            dur = ev
                    evs.append((mid, off, dur))
            lines[lname] = [(m, ts + o // 1000, ts + (o + d) // 1000)
                            for m, o, d in evs]
        elif num in (4, 5):                     # map entries: key 1, value 2
            val = next((mv for mn, mv in _fields(b, *v) if mn == 2), None)
            if val is None:
                continue
            mid, mname, mstats = 0, "", []
            for fn, fv in _fields(b, *val):
                if fn == 1:
                    mid = fv
                elif fn == 2:
                    mname = _str(b, fv)
                elif fn == 5 and num == 4:              # XEventMetadata stats
                    mstats.append(fv)
            if num == 4:
                names[mid] = mname
                raw_stats[mid] = mstats
            else:
                stat_names[mid] = mname
    stats = {}
    for mid, sls in raw_stats.items():
        got = {}
        for sl in sls:
            key = val = None
            for fn, fv in _fields(b, *sl):
                if fn == 1:
                    key = stat_names.get(fv)
                elif fn in (3, 4):                      # u64, i64
                    val = fv
                elif fn == 7:                           # ref: a name
                    val = stat_names.get(fv)
                elif fn == 5:
                    val = _str(b, fv)
            if key in _STATS:
                got[key] = val
        stats[mid] = got
    return Plane(name, lines, names, stats)


def read_xspace(path: str) -> list:
    """The planes of an ``.xplane.pb`` (or ``.xplane.pb.gz``): each line's
    events as (metadata id, start ns, end ns), and the ``_STATS`` of each
    event's metadata. A stat given by reference (``ref_value``) is the id
    of another stat's metadata, whose name is the value."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        b = f.read()
    return [_plane(b, *v) for num, v in _fields(b) if num == 1]


# -- sorting one op -----------------------------------------------------

def _unwrap(component: str) -> str:
    while (m := _WRAPPER.match(component)):
        component = m.group(1)
    return component


def classify_one(stack: str) -> tuple[str, tuple[str, ...]]:
    """(direction, scope path) of one name stack; the path is empty when
    no program scope names the op."""
    parts = stack.split("/")
    parts[-1] = parts[-1].split(":")[0]        # "add:" -> "add"
    if "rematted_computation" in parts:
        direction = RECOMPUTE
    elif any("transpose(" in p for p in parts):
        direction = BACKWARD
    elif any("jvp(" in p for p in parts):
        direction = FORWARD
    else:
        direction = OUTSIDE
    path = tuple(n for n in map(_unwrap, parts) if n in KNOWN)
    if path and path[0] not in STEP_PARTS:
        path = (PART_OF[path[0]],) + path
    return direction, path


def classify(tf_op: str | None) -> tuple[str, str]:
    """(direction, scope path) of an op's ``tf_op``. An op fused from
    several name stacks (``a;b``) takes the first of them that a scope
    names. No ``tf_op``: ``(no name)``; no scope: ``(unscoped)``."""
    if not tf_op:
        return OUTSIDE, NO_NAME
    stacks = [classify_one(s) for s in tf_op.split(";") if s]
    for direction, path in stacks:
        if path:
            return direction, "/".join(path)
    return (stacks[0][0] if stacks else OUTSIDE), UNSCOPED


# -- the reduction ------------------------------------------------------

@dataclasses.dataclass
class ScopeTimes:
    seconds: dict            # (direction, scope path) -> s, averaged
    busy_s: float            # the step module's busy time, averaged
    window_s: float
    n_devices: int

    def total(self, part: str, *directions: str) -> float:
        """Seconds under step part ``part`` (``model``, ``optimizer``, ...)
        in ``directions`` (all when none are given)."""
        return sum(s for (d, p), s in self.seconds.items()
                   if p.split("/")[0] == part
                   and (not directions or d in directions))

    def top(self, steps: int, n: int = TOP) -> list:
        """The ``n`` largest entries as ["direction/path", ms per step]."""
        rows = sorted(self.seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{d}/{p}", 1e3 * s / steps] for (d, p), s in rows]


def reduce(planes: list) -> ScopeTimes | None:
    """The step module's device self-time in the window by (direction,
    scope path), averaged over devices; None for a program that names no
    scope. Raises when named ops under no scope pass the limit."""
    host = next((p for p in planes if p.name == "/host:CPU"
                 and any(p.names.get(m) == WINDOW_SPAN
                         for ln in p.lines.values() for m, _, _ in ln)),
                None)
    devices = [p for p in planes if p.name.startswith("/device:TPU:")
               and "Core" not in p.name.split(":")[-1]
               and "XLA Ops" in p.lines]
    if host is None or not devices:
        raise ValueError("the trace holds no window span or no device ops")
    lo, hi = next((s, e) for ln in host.lines.values() for m, s, e in ln
                  if host.names.get(m) == WINDOW_SPAN)
    seconds: dict[tuple[str, str], float] = {}
    busy = 0
    for p in devices:
        step_ids = {int(m.group(1)) % _U64
                    for mid, _, _ in p.lines.get("XLA Modules", [])
                    if (m := _MODULE.fullmatch(p.names.get(mid, "")))}
        ops = [(m, max(s, lo), min(e, hi)) for m, s, e in p.lines["XLA Ops"]
               if e > lo and s < hi]
        own = self_times([Ev("", s, e) for _, s, e in ops])
        spans = []
        for (m, s, e), t in zip(ops, own):
            st = p.stats.get(m, {})
            if st.get("program_id", -1) % _U64 not in step_ids:
                continue
            key = classify(st.get("tf_op"))
            seconds[key] = seconds.get(key, 0.0) + t / 1e9
            spans.append((s, e))
        busy += sum(e - s for s, e in union(spans))
    n = len(devices)
    out = ScopeTimes({k: v / n for k, v in seconds.items()}, busy / n / 1e9,
                     (hi - lo) / 1e9, n)
    named = sum(s for (d, p), s in out.seconds.items()
                if p not in (NO_NAME, UNSCOPED))
    if named == 0:
        return None             # a program that names none of its parts
    unscoped = sum(s for (d, p), s in out.seconds.items() if p == UNSCOPED)
    if unscoped > UNSCOPED_LIMIT * out.busy_s:
        raise ValueError(
            f"scopes: {unscoped:.6f} s of the step's {out.busy_s:.6f} s "
            f"busy time sits under no program scope (limit "
            f"{UNSCOPED_LIMIT:.0%}); a scope has dropped out of the "
            f"program")
    return out


def of_run(run) -> ScopeTimes | None:
    """The scope reduction of a traced training run, made once and kept
    in ``run.extra["scopes"]``; None for an untraced run or a program
    that names no scope. The first call prints the largest entries to
    standard error and writes all of them to ``OUT_DIR/scopes.json``."""
    if run.kind != "train" or run.trace is None or run.steps == 0:
        return None
    if "scopes" in run.extra:
        return run.extra["scopes"]
    files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no trace under {TRACE_DIR}")
    got = reduce(read_xspace(files[0]))
    if got is not None and abs(got.window_s - run.trace.window_s) > 1e-6:
        raise ValueError(f"scopes: the trace's window ({got.window_s} s) "
                         f"is not the run's ({run.trace.window_s} s)")
    run.extra["scopes"] = got
    if got is not None:
        table = {"steps": run.steps, "busy_ms": 1e3 * got.busy_s / run.steps,
                 "top_ms": got.top(run.steps, n=len(got.seconds))}
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        (OUT_DIR / "scopes.json").write_text(json.dumps(table))
        print("scopes: " + json.dumps(dict(table, top_ms=table["top_ms"][:TOP])),
              file=sys.stderr)
    return got


def ms_per_step(run, part: str, *directions: str) -> float | None:
    """Device self-time per step under ``part`` in ``directions``."""
    got = of_run(run)
    if got is None:
        return None
    return 1e3 * got.total(part, *directions) / run.steps
