"""The reduction of device self-time by the program's scopes: the sorting
of name stacks, the reduction on hand-made planes, the decoder against
``ProfileData``, and the recorded traces (one of a program without
scopes, one with them)."""
import dataclasses
import json
from pathlib import Path

import pytest

from harness import scopes as SC
from harness import trace as T
from harness.scopes import Plane

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "yi-9b.train.4k.xplane.pb.gz"     # the program before scopes

STEP = "jit(train_step)"


@pytest.mark.parametrize("tf_op, want", [
    # the forms seen in a v5e trace of the step, with the program's scopes
    (f"{STEP}/jvp(model)/while/body/closed_call/attention/"
     "bqhd,bkhd->bhqk/dot_general:", ("forward", "model/attention")),
    (f"{STEP}/transpose(jvp(model))/while/body/closed_call/checkpoint/"
     "mlp/...d,df->...f/dot_general:", ("backward", "model/mlp")),
    (f"{STEP}/transpose(jvp(model))/while/body/closed_call/checkpoint/"
     "rematted_computation/norm/mul:", ("recompute", "model/norm")),
    (f"{STEP}/transpose(jvp(model))/head/dot_general:",
     ("backward", "model/head")),
    (f"{STEP}/jvp(model)/loss/jit(take_along_axis)/gather:",
     ("forward", "model/loss")),
    (f"{STEP}/jvp(model)/embed/jit(_take)/gather:",
     ("forward", "model/embed")),
    (f"{STEP}/optimizer/sr_bits/iota_2x32_shape:",
     ("outside", "optimizer/sr_bits")),
    (f"{STEP}/optimizer/layout/reshape;{STEP}/optimizer/layout/reshape:",
     ("outside", "optimizer/layout")),
    (f"{STEP}/optimizer/fused_adamw/pallas_call:",
     ("outside", "optimizer/fused_adamw")),
    (f"{STEP}/transport/convert_element_type:", ("outside", "transport")),
    (f"{STEP}/step_metrics/reduce_sum:", ("outside", "step_metrics")),
    # a scope whose step part the compiler dropped from the stack
    (f"{STEP}/attention/closed_call/while/body/closed_call/le:",
     ("outside", "model/attention")),
    # a fusion of an unnamed and a named stack takes the named one
    (f"{STEP}/add;{STEP}/optimizer/layout/pad:",
     ("outside", "optimizer/layout")),
    # the forms of the program before scopes: nothing names them
    (f"{STEP}/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/exp:", ("recompute", SC.UNSCOPED)),
    (f"{STEP}/transpose(jvp())/while/body/add:", ("backward", SC.UNSCOPED)),
    (f"{STEP}/jvp()/while/body/closed_call/closed_call/exp:",
     ("forward", SC.UNSCOPED)),
    (f"{STEP}/jit(_threefry_split)/fused_adamw_optimizer.<locals>.update:",
     ("outside", SC.UNSCOPED)),
    (f"{STEP}/pallas_call:", ("outside", SC.UNSCOPED)),
    # a jitted helper whose name is a scope's is not that scope
    (f"{STEP}/jvp(model)/jit(norm)/sqrt:", ("forward", "model")),
    ("", ("outside", SC.NO_NAME)),
    (None, ("outside", SC.NO_NAME)),
])
def test_classify(tf_op, want):
    assert SC.classify(tf_op) == want


def _planes(ops, *, window=(0, 1000), module="jit_train_step(7)",
            pid=7):
    """A host plane with the window span and one device plane whose
    ``XLA Ops`` line holds ``ops``: (tf_op or None, start, end, in the
    step's module)."""
    names, stats, line = {1: module}, {1: {}}, []
    for i, (tf_op, s, e, in_step) in enumerate(ops, start=2):
        names[i] = f"op{i}"
        stats[i] = {"program_id": pid if in_step else pid + 1}
        if tf_op is not None:
            stats[i]["tf_op"] = tf_op
        line.append((i, s, e))
    dev = Plane("/device:TPU:0", {"XLA Modules": [(1, 0, 1000)],
                                  "XLA Ops": line}, names, stats)
    host = Plane("/host:CPU", {"main": [(1, *window)]},
                 {1: T.WINDOW_SPAN}, {})
    return [host, dev]


FWD = f"{STEP}/jvp(model)/mlp/dot_general:"
BWD = f"{STEP}/transpose(jvp(model))/mlp/dot_general:"
OPT = f"{STEP}/optimizer/fused_adamw/pallas_call:"


def test_reduce_hand_made():
    r = SC.reduce(_planes([
        (FWD, 0, 100, True),
        (BWD, 100, 300, True),
        (f"{STEP}/transpose(jvp(model))/while:", 300, 600, True),
        (f"{STEP}/transpose(jvp(model))/while/body/"
         "rematted_computation/mlp/mul:", 350, 400, True),  # in the while
        (OPT, 600, 700, True),
        (None, 700, 750, True),                     # a copy, no name
        (f"{STEP}/add:", 750, 751, True),           # unscoped, 0.1%
        ("jit(_rows)/add:", 800, 900, False),       # another module
        (FWD, 950, 1100, True),                     # clipped at 1000
    ]))
    ns = 1e-9
    assert r.n_devices == 1 and r.window_s == pytest.approx(1000 * ns)
    assert r.busy_s == pytest.approx(801 * ns)      # [0,751] + [950,1000]
    assert r.total("model", SC.FORWARD) == pytest.approx(150 * ns)
    assert r.total("model", SC.BACKWARD) == pytest.approx(450 * ns)
    assert r.total("model", SC.RECOMPUTE) == pytest.approx(50 * ns)
    assert r.total("optimizer") == pytest.approx(100 * ns)
    assert r.seconds[("outside", SC.NO_NAME)] == pytest.approx(50 * ns)
    assert r.seconds[("outside", SC.UNSCOPED)] == pytest.approx(1 * ns)
    assert sum(r.seconds.values()) == pytest.approx(r.busy_s)
    top = r.top(steps=1, n=2)             # ms per step, largest first
    assert top == [["backward/model", pytest.approx(250e-6)],   # the while
                   ["backward/model/mlp", pytest.approx(200e-6)]]


def test_reduce_raises_over_one_percent_unscoped():
    ops = [(FWD, 0, 980, True), (f"{STEP}/add:", 980, 991, True)]
    with pytest.raises(ValueError, match="under no program scope"):
        SC.reduce(_planes(ops))
    ops[-1] = (f"{STEP}/add:", 980, 989, True)      # 9 of 989: under 1%
    assert SC.reduce(_planes(ops)).seconds[("outside", SC.UNSCOPED)] == \
        pytest.approx(9e-9)


def test_unnamed_program_reduces_to_none():
    ops = [(f"{STEP}/jvp()/dot_general:", 0, 500, True),
           (None, 500, 600, True)]
    assert SC.reduce(_planes(ops)) is None


def test_module_matched_by_program_id():
    # an op of the step's module with an id written as signed 64 bits
    big = (1 << 64) - 5
    planes = _planes([(FWD, 0, 100, True)], module=f"jit_train_step({big})",
                     pid=big)
    assert SC.reduce(planes).total("model") == pytest.approx(100e-9)
    planes = _planes([(FWD, 0, 100, True)], module="jit_train_step(-5)",
                     pid=big)
    assert SC.reduce(planes).total("model") == pytest.approx(100e-9)


def test_decoder_agrees_with_profile_data():
    """The XSpace read directly holds the events ``ProfileData`` gives,
    within the nanosecond its float rounding moves them."""
    devices, host = T.events_of(str(OLD))
    planes = SC.read_xspace(str(OLD))
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    mine = dev.lines["XLA Ops"]
    assert len(mine) == len(devices[0]) == 50300
    for (m, s, e), ev in zip(mine, devices[0]):
        assert dev.names[m] == ev.name
        assert abs(s - ev.start) <= 1 and abs(e - ev.end) <= 1
    tf = [st["tf_op"] for st in dev.stats.values() if "tf_op" in st]
    assert len(tf) == 712
    assert all("program_id" in st for st in dev.stats.values()
               if "tf_op" in st)


def test_old_trace_by_direction():
    """The program before scopes: no scope, so the reduction is None; the
    directions alone, read with the scopes' own sorting, give the shares
    counted by hand from the same trace (backward 32.6%, forward 18.8%,
    recompute 12.0% of the step's busy time)."""
    planes = SC.read_xspace(str(OLD))
    assert SC.reduce(planes) is None
    dev = next(p for p in planes if p.name == "/device:TPU:0")
    host = next(p for p in planes if p.name == "/host:CPU"
                and T.WINDOW_SPAN in p.names.values())
    # name every op ("model") so the reduction keeps its directions
    named = dataclasses.replace(dev, stats={
        m: dict(st, tf_op=st["tf_op"].replace(f"{STEP}/",
                                               f"{STEP}/model/", 1))
        if "tf_op" in st else st for m, st in dev.stats.items()})
    r = SC.reduce([host, named])
    assert r.busy_s == pytest.approx(10.197711, abs=1e-6)
    assert sum(r.seconds.values()) == pytest.approx(r.busy_s, rel=1e-9)
    share = {d: r.total("model", d) / r.busy_s
             for d in (SC.FORWARD, SC.BACKWARD, SC.RECOMPUTE)}
    assert share[SC.BACKWARD] == pytest.approx(0.326, abs=5e-4)
    assert share[SC.FORWARD] == pytest.approx(0.188, abs=5e-4)
    assert share[SC.RECOMPUTE] == pytest.approx(0.120, abs=5e-4)
    assert r.seconds[("outside", SC.NO_NAME)] / r.busy_s == pytest.approx(
        0.0193, abs=5e-4)


def test_names_match_the_program():
    from repro.profile import scopes as P
    assert SC.STEP_PARTS == P.STEP_SCOPES
    assert set(SC.PART_OF) == set(P.MODEL_SCOPES + P.OPTIMIZER_SCOPES + (
        P.FUSED_ADAMW, P.FUSED_SGD))
    assert all(SC.PART_OF[n] == P.MODEL for n in P.MODEL_SCOPES)
    assert all(SC.PART_OF[n] == P.OPTIMIZER for n in P.OPTIMIZER_SCOPES)


NEW = DATA / "yi-9b.train.4k.scopes.xplane.pb.gz"  # the program with scopes


@pytest.fixture(scope="module")
def scoped():
    return SC.reduce(SC.read_xspace(str(NEW)))


def test_recorded_scoped_trace(scoped):
    """Three steps of ``yi-9b.train.4k`` on a v5e, the program with its
    scopes. Read off the trace by hand (TensorFlow's own xplane proto,
    not this decoder): a 1.227117593 s window holding 3 ``jit_train_step``
    modules of 1.223801944 s in all; the step's ops whose ``tf_op``
    holds ``/optimizer/`` last 0.416701688 s (657 events): of them
    ``/optimizer/layout/`` 0.190931948 s (411), ``/optimizer/sr_bits/``
    0.116765505 s (207) and ``/optimizer/fused_adamw/`` 0.109003213 s,
    the 36 ``tpu_custom_call`` events (12 leaves x 3 steps);
    ``/step_metrics/`` 0.006014965 s (33). No named op of the step sits
    under no scope."""
    r = scoped
    assert r.n_devices == 1
    assert r.window_s == pytest.approx(1.227117593, abs=1e-6)
    # the modules' spans hold gaps between their ops
    assert r.busy_s == pytest.approx(1.223801944, abs=1e-4)
    assert r.busy_s < 1.223801944
    assert r.total("optimizer") == pytest.approx(0.416701688, abs=1e-6)
    for path, s in [("optimizer/layout", 0.190931948),
                    ("optimizer/sr_bits", 0.116765505),
                    ("optimizer/fused_adamw", 0.109003213),
                    ("step_metrics", 0.006014965)]:
        assert r.seconds[(SC.OUTSIDE, path)] == pytest.approx(s, abs=1e-6)
    assert r.total("transport") == 0.0          # one chip: nothing to move
    assert not [k for k in r.seconds if k[1] == SC.UNSCOPED]
    # every op of the step is counted once: the parts sum to busy time
    assert sum(r.seconds.values()) == pytest.approx(r.busy_s, rel=1e-9)
    model = sum(r.total("model", d) for d in
                (SC.FORWARD, SC.BACKWARD, SC.RECOMPUTE, SC.OUTSIDE))
    rest = (r.total("optimizer") + r.total("step_metrics")
            + r.seconds[(SC.OUTSIDE, SC.NO_NAME)])
    assert model + rest == pytest.approx(r.busy_s, rel=1e-9)
    # the shares the metrics read, per step (3 steps): within the ranges
    # predicted from the older trace's count by hand
    ms = {d: 1e3 * r.total("model", d) / 3
          for d in (SC.FORWARD, SC.BACKWARD, SC.RECOMPUTE)}
    assert 70 < ms[SC.FORWARD] < 85
    assert 120 < ms[SC.BACKWARD] < 145
    assert 40 < ms[SC.RECOMPUTE] < 55
    assert 125 < 1e3 * r.total("optimizer") / 3 < 155


def test_roofline_kernel_count_with_the_kernel_named():
    """``name="fused_adamw"`` names the custom call and its outputs'
    tuple elements; only the call is an event, so the roofline's pattern
    still finds one event per leaf and step."""
    from harness import spec
    reader = spec.metric_reader("fused_adamw_roofline")
    r = T.reduce(*T.events_of(str(NEW)))
    pattern = reader.__globals__["KERNEL"]
    assert r.kernel_n(pattern) == 3 * 12
    assert r.kernel_s(pattern) == pytest.approx(0.109003213, abs=1e-6)


def _record(trace_file, tmp_path, monkeypatch, steps):
    """A traced run's record whose trace is ``trace_file``, laid where
    the run's tracer writes it."""
    import gzip
    import shutil
    from harness import spec
    from harness.train import Record
    out = tmp_path / "out"
    (out / "trace" / "run").mkdir(parents=True)
    with gzip.open(trace_file, "rb") as f, \
            open(out / "trace" / "run" / "host.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    monkeypatch.setattr(SC, "OUT_DIR", out)
    monkeypatch.setattr(SC, "TRACE_DIR", out / "trace")
    red = T.reduce(*T.events_of(str(trace_file)))
    cell = spec.load_cell("yi-9b.train.4k")
    return Record("train", cell, red.window_s, steps, steps * 4096,
                  trace=red, device_kind="TPU v5 lite",
                  extra={"setup_s": 20.0, "param_leaves": 12})


READERS = ["forward_ms.train", "backward_ms.train", "recompute_ms.train",
           "optimizer_ms.train"]


def test_readers_on_the_scoped_trace(tmp_path, monkeypatch, scoped, capsys):
    from harness import spec
    rec = _record(NEW, tmp_path, monkeypatch, steps=3)
    got = {m: spec.metric_reader(m)(rec) for m in READERS}
    assert got == pytest.approx({
        "forward_ms.train": 1e3 * scoped.total("model", SC.FORWARD) / 3,
        "backward_ms.train": 1e3 * scoped.total("model", SC.BACKWARD) / 3,
        "recompute_ms.train": 1e3 * scoped.total("model", SC.RECOMPUTE) / 3,
        "optimizer_ms.train": 1e3 * 0.416701688 / 3}, rel=1e-6)
    # the four, with the rest of the step, make up its busy time
    rest = (scoped.total("model", SC.OUTSIDE) + scoped.total("transport")
            + scoped.total("step_metrics")
            + scoped.seconds[(SC.OUTSIDE, SC.NO_NAME)])
    assert sum(got.values()) + 1e3 * rest / 3 == pytest.approx(
        1e3 * scoped.busy_s / 3, rel=1e-9)
    # made once, kept on the record, written out and shown on stderr
    assert rec.extra["scopes"] is not None
    table = json.loads((tmp_path / "out" / "scopes.json").read_text())
    assert table["steps"] == 3 and len(table["top_ms"]) == len(scoped.seconds)
    assert table["top_ms"][0][0] == "outside/optimizer/layout"
    err = capsys.readouterr().err
    assert err.count("scopes: ") == 1


def test_readers_silent_for_a_program_without_scopes(tmp_path, monkeypatch):
    from harness import spec
    rec = _record(OLD, tmp_path, monkeypatch, steps=25)
    assert all(spec.metric_reader(m)(rec) is None for m in READERS)
    assert not (tmp_path / "out" / "scopes.json").exists()


def test_readers_silent_without_a_trace():
    from harness import spec
    from harness.train import Record
    rec = Record("train", spec.load_cell("yi-9b.train.4k"), 1.0, 1, 4096)
    assert all(spec.metric_reader(m)(rec) is None for m in READERS)


def test_reader_refuses_another_runs_trace(tmp_path, monkeypatch):
    import dataclasses as dc
    from harness import spec
    rec = _record(NEW, tmp_path, monkeypatch, steps=3)
    rec.trace = dc.replace(rec.trace, window_s=rec.trace.window_s + 1.0)
    with pytest.raises(ValueError, match="is not the run's"):
        spec.metric_reader("optimizer_ms.train")(rec)


@pytest.mark.parametrize("trace_file, steps", [(NEW, 3), (OLD, 25)],
                         ids=["scoped", "unscoped"])
def test_measure_puts_the_scopes_in_the_last_line(trace_file, steps,
                                                  tmp_path, monkeypatch):
    """A traced run's result line carries the step's largest scopes, in ms
    per step (none for a program that names no scope), and still ends
    with the compared numbers."""
    import types

    import run
    from harness import train
    rec = _record(trace_file, tmp_path, monkeypatch, steps=steps)
    rec.extra.update(readings={"program": {}}, memory_peak_bytes=1)
    monkeypatch.setattr(train, "run", lambda *a, **kw: rec)
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "out" / "trace")
    chip = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    out = run.measure(rec.cell, 1, 1.0, trace=True, devices=[chip],
                      t_start=0.0)
    if trace_file == NEW:
        want = SC.reduce(SC.read_xspace(str(NEW))).top(steps)
        assert out["scopes"] == want and len(want) == SC.TOP
        assert out["scopes"][0][0] == "outside/optimizer/layout"
        assert "optimizer_ms.train" in out["metrics"]
    else:
        assert "scopes" not in out
        assert "optimizer_ms.train" not in out["metrics"]
    assert list(out)[-1] == "checks"
