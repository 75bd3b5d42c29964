"""The reduction of the program's own spans and engine phases
(``harness/program_trace.py``) and the readers of the metrics it feeds:
on hand-made planes, and on 3 ms slices of traced runs recorded on a TPU
v5e (cut by ``make_span_fixture.py``)."""
import gzip
import json
from pathlib import Path

import pytest

from harness import core, program_trace as pt, trace

DATA = Path(__file__).parent / "data"
FIXTURES = sorted(DATA.glob("*_spans.json.gz"))
PHASES_SWEEP = ("admit_place", "activate", "rates", "advance", "complete")
NEW_METRICS = ("step_admit_us.sweep", "step_activate_us.sweep",
               "step_rates_us.sweep", "step_advance_us.sweep",
               "step_complete_us.sweep", "routes_ms.sweep",
               "boundary_idle_share.grid")


@pytest.mark.parametrize("op_name,phase", [
    ("jit(call)/vmap()/while/body/cond/branch_1_fun/rates/reduce_min",
     "rates"),
    ("jit(chunk)/while/body/vmap(activate)/while/body/gather", "activate"),
    ("jit(chunk)/while/body/vmap(vmap(admit_place))/scatter", "admit_place"),
    ("jit(f)/while;jit(f)/while/body/complete/sub", "complete"),
    ("jit(f)/while/body_pred/not", ""),
    ("jit(rates)/add", ""),              # a function named like a phase
    ("jit(f)/while/body/advanced/add", ""),
    ("", ""),
])
def test_op_phase(op_name, phase):
    assert pt.op_phase(op_name) == phase


def _pb(*fields):
    """A protobuf message of (field number, value) pairs: an int is a
    varint, bytes or str a length-delimited field."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_op_names_read_off_the_wire_format(tmp_path):
    def op(md_id, name, stats):
        return _pb((4, _pb((1, md_id), (2, _pb((1, md_id), (2, name),
                                                *[(5, s) for s in stats])))))

    tf_op, other = _pb((1, 7), (2, "tf_op")), _pb((1, 8), (2, "flops"))
    named = _pb((1, 9), (2, "jit(f)/while/body/advance/add:"))
    device = (_pb((1, 3), (2, "/device:TPU:0"),
                  (3, _pb((2, "a line the reader skips"))))
              + op(1, "%fusion.1 = f32[8]",
                   [_pb((1, 8), (4, 12)),
                    _pb((1, 7), (5, "jit(f)/while/body/rates/mul:"))])
              + op(2, "%fusion.2 = f32[8]", [_pb((1, 7), (7, 9))])
              + op(3, "%copy.3 = f32[8]", [_pb((1, 8), (4, 1))])
              + _pb((5, _pb((1, 7), (2, tf_op))), (5, _pb((1, 8), (2, other))),
                    (5, _pb((1, 9), (2, named)))))
    host = _pb((2, "/host:CPU")) + op(1, "%fusion.1 = f32[8]",
                                      [_pb((1, 1), (5, "x"))])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, device), (1, host), (2, "an error")))
    assert pt.op_names(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8]": "jit(f)/while/body/rates/mul",
        "%fusion.2 = f32[8]": "jit(f)/while/body/advance/add"}}


def _planes():
    """A window [0, 100) of one call: the front door's setup and routes,
    then dispatch; device ops in phases, a while loop nesting one."""
    host = {"main": [
        ("bench.window", 0, 100), ("bench.call", 0, 100),
        ("bench.build", 0, 30), ("repro.front.setup", 0, 20),
        ("repro.front.routes", 2, 12), ("repro.front.consts", 20, 28),
        ("repro.run.dispatch", 30, 34), ("other.span", 0, 100)]}
    dev = {"XLA Ops": [
        ("fusion.1", 25, 27, ""),                              # consts copy
        ("%while.3 = (f32[])", 40, 90, "jit(c)/vmap()/while"),
        ("fusion.4", 42, 60, "jit(c)/vmap()/while/body/rates/mul"),
        ("fusion.5", 60, 70, "jit(c)/while/body/vmap(activate)/gather"),
        ("fusion.6", 75, 80, "jit(c)/while/body/complete/add"),
        ("fusion.7", 95, 120, "jit(c)/while/body/advance/add")]}
    return [("/host:CPU", host), ("/device:TPU:0", dev)]


def test_reduce_hand_made():
    r = pt.reduce_planes(_planes())
    assert r["window_s"] == pytest.approx(100e-9)
    # busy [25, 27) + [40, 90) + [95, 100)
    assert r["busy_s"] == pytest.approx(57e-9)
    ph = r["device_phases"]
    assert ph["rates"] == pytest.approx(18e-9)
    assert ph["activate"] == pytest.approx(10e-9)
    assert ph["complete"] == pytest.approx(5e-9)
    assert ph["advance"] == pytest.approx(5e-9)        # cut at the window
    # the consts copy and the loop's own time (50 - 33 nested)
    assert ph["unscoped"] == pytest.approx((2 + 17) * 1e-9)
    assert ph["admit_place"] == ph["chaos"] == 0.0
    assert sum(ph.values()) == pytest.approx(r["busy_s"])
    gaps = dict(r["idle_gaps"])
    # idle [0, 25), [27, 40), [90, 95), each instant to the innermost span
    # of either prefix open then
    assert gaps == {
        "repro.front.setup": pytest.approx(10e-9),    # [0, 2), [12, 20)
        "repro.front.routes": pytest.approx(10e-9),   # [2, 12)
        "repro.front.consts": pytest.approx(6e-9),    # [20, 25), [27, 28)
        "bench.build": pytest.approx(2e-9),           # [28, 30)
        "repro.run.dispatch": pytest.approx(4e-9),    # [30, 34)
        "bench.call": pytest.approx(11e-9)}           # [34, 40), [90, 95)
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_gap_goes_to_the_innermost_span_of_either_prefix():
    host = {"main": [("bench.window", 0, 100), ("bench.campaign", 0, 100),
                     ("repro.fleet.retire", 10, 30),
                     ("repro.fleet.refill", 50, 60)]}
    dev = {"XLA Ops": [("f", 0, 10, ""), ("g", 30, 50, ""),
                       ("h", 60, 90, "")]}
    r = pt.reduce_planes([("/host:CPU", host), ("/device:TPU:0", dev)])
    gaps = dict(r["idle_gaps"])
    assert gaps["repro.fleet.retire"] == pytest.approx(20e-9)
    assert gaps["repro.fleet.refill"] == pytest.approx(10e-9)
    assert gaps["bench.campaign"] == pytest.approx(10e-9)
    # the same gaps by trace.py, which sees only bench.* spans
    old = trace.reduce_planes([("/host:CPU", host), ("/device:TPU:0", {
        "XLA Ops": [(n, a, b) for n, a, b, _ in dev["XLA Ops"]]})])
    assert dict(old["idle_gaps"]) == {"campaign": pytest.approx(40e-9)}
    assert old["busy_s"] == pytest.approx(r["busy_s"])


def test_a_session_cut_inside_a_unit_leaves_an_untraced_tail():
    """No closed window: the window runs from the open marker to the
    session's end, and idle time after the last recorded span is the
    session stopping, not host work between spans."""
    host = {"main": [("bench.window_open", 0, 0),
                     ("repro.fleet.sync", 5, 40),
                     ("repro.fleet.retire", 40, 50),
                     ("repro.fleet.chunk", 50, 60)]}
    dev = {"XLA Ops": [("f", 0, 40, ""), ("g", 58, 80, "")]}
    r = pt.reduce_planes([("/host:CPU", host), ("/device:TPU:0", dev),
                          (trace.SESSION, {"span": [("session", 0, 100)]})])
    assert r["window_s"] == pytest.approx(100e-9)
    assert dict(r["idle_gaps"]) == {
        "repro.fleet.retire": pytest.approx(10e-9),
        "repro.fleet.chunk": pytest.approx(8e-9),
        pt.UNTRACED: pytest.approx(20e-9)}


def test_no_window_or_no_device_reads_nothing():
    planes = _planes()
    assert pt.reduce_planes(planes[1:]) is None
    assert pt.reduce_planes(planes[:1]) is None


def _ctx(planes, units, cell="paper-sweep"):
    return {"cell": {"name": cell}, "trace": {"window_s": 1.0},
            "units": units, "program_trace": pt.reduce_planes(planes)}


def test_readers_hand_made():
    ctx = _ctx(_planes(), [{"steps": 4, "traced": True},
                           {"steps": 9, "traced": False}])
    read = {m: core.metric_reader(m) for m in NEW_METRICS}
    assert read["step_rates_us.sweep"](ctx) == pytest.approx(
        1e6 * 18e-9 / 4)
    assert read["step_activate_us.sweep"](ctx) == pytest.approx(
        1e6 * 10e-9 / 4)
    assert read["step_admit_us.sweep"](ctx) == 0.0
    assert read["routes_ms.sweep"](ctx) == pytest.approx(10e-6)
    # no fleet span in this trace
    assert read["boundary_idle_share.grid"](ctx) is None


def test_boundary_idle_share_hand_made():
    host = {"main": [("bench.window", 0, 100),
                     ("repro.fleet.chunk", 0, 5),
                     ("repro.fleet.sync", 5, 40),
                     ("repro.fleet.retire", 40, 70),
                     ("repro.fleet.refill", 70, 80)]}
    dev = {"XLA Ops": [("f", 2, 40, "jit(c)/while/body/rates/x"),
                       ("g", 80, 90, "")]}
    ctx = _ctx([("/host:CPU", host), ("/device:TPU:0", dev)], [],
               cell="paper-fleet")
    # idle [0, 2) chunk, [40, 80) retire and refill, [90, 100) between
    share = core.metric_reader("boundary_idle_share.grid")(ctx)
    assert share == pytest.approx(42.0)
    idle = 100.0 * (1 - ctx["program_trace"]["busy_s"] / 100e-9)
    assert share <= idle


def test_readers_find_nothing_in_a_program_without_spans():
    """The parent of the spans: bench.* spans and unscoped ops only."""
    host = {"main": [("bench.window", 0, 100), ("bench.call", 0, 100),
                     ("bench.build", 0, 30)]}
    dev = {"XLA Ops": [("f", 40, 90, "jit(c)/vmap()/while/body/mul")]}
    ctx = _ctx([("/host:CPU", host), ("/device:TPU:0", dev)],
               [{"steps": 4, "traced": True}])
    for m in NEW_METRICS:
        assert core.metric_reader(m)(ctx) is None, m


def test_untraced_run_reads_nothing():
    ctx = {"cell": {"name": "paper-sweep"}, "trace": None, "units": []}
    for m in NEW_METRICS:
        assert core.metric_reader(m)(ctx) is None, m


def test_new_metrics_are_declared():
    with open(core.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for m in NEW_METRICS:
        assert m in declared
        assert (core.BENCH / "metrics" / f"{m}.py").exists()


def _load(fixture):
    with gzip.open(fixture, "rt") as f:
        return [(p, {k: [tuple(e) for e in v] for k, v in lines.items()})
                for p, lines in json.load(f)]


def test_recorded_fixtures_exist():
    assert {f.name for f in FIXTURES} >= {"paper-sweep_spans.json.gz",
                                          "paper-fleet_spans.json.gz"}


@pytest.mark.parametrize("fixture", FIXTURES, ids=lambda p: p.name)
def test_recorded_tpu_spans(fixture):
    planes = _load(fixture)
    r = pt.reduce_planes(planes)
    old = trace.reduce_planes([(p, {k: [e[:3] for e in v]
                                    for k, v in lines.items()})
                               for p, lines in planes])
    # the same busy time and window as trace.py's reduction
    assert r["busy_s"] == pytest.approx(old["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(old["window_s"], rel=1e-9)
    # self times by phase account for the busy time; the engine's phases
    # hold most of it
    phases = r["device_phases"]
    assert sum(phases.values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert phases["unscoped"] < 0.5 * r["busy_s"]
    # every idle instant is attributed once
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_recorded_sweep_steps_through_every_phase():
    r = pt.reduce_planes(_load(DATA / "paper-sweep_spans.json.gz"))
    ph = r["device_phases"]
    assert all(ph[p] > 0 for p in PHASES_SWEEP), ph
    assert max(ph, key=ph.get) == "rates"


def test_recorded_fleet_boundary_is_attributed():
    planes = _load(DATA / "paper-fleet_spans.json.gz")
    r = pt.reduce_planes(planes)
    assert {n for n, _, _ in r["spans"]} >= {
        "repro.fleet.refill", "repro.fleet.chunk"}
    ctx = {"cell": {"name": "paper-fleet"}, "trace": {}, "units": [],
           "program_trace": r}
    share = core.metric_reader("boundary_idle_share.grid")(ctx)
    idle = 100.0 * (1.0 - r["busy_s"] / r["window_s"])
    assert 0 < share <= idle * (1 + 1e-9)
