"""The ``step_fail_us.grid`` reader on hand-made planes: self time of the
operations under the engine's ``fail_transitions`` scope, inside the
window, per engine step."""
import importlib.util

import pytest

from harness import core, trace

spec = importlib.util.spec_from_file_location(
    "fail_reader", core.BENCH / "metrics" / "step_fail_us.grid.py")
reader = importlib.util.module_from_spec(spec)
spec.loader.exec_module(reader)

FAIL = ("jit(chunk)/while/body/vmap(chaos)/cond/branch_1_fun/"
        "fail_transitions/select_n")


@pytest.mark.parametrize("op_name,inside", [
    (FAIL, True),
    ("jit(f)/while/body/cond/branch_0_fun/chaos/cond/branch_1_fun/"
     "fail_transitions/while/body/add", True),
    ("jit(f)/while/body/chaos/vmap(fail_transitions)/min", True),
    ("jit(f)/while/body/chaos/vmap()/le", False),
    ("jit(f)/while/body/vmap(chaos)/reduce_or", False),
    ("jit(f)/while/body/chaos/fail_transitions_x/add", False),
    ("", False),
])
def test_in_scope(op_name, inside):
    assert reader.in_scope(op_name) is inside


def _planes(ops):
    host = ("/host:CPU", {"main": [(trace.WINDOW, 100, 1100)]})
    return [host, ("/device:TPU:0", {"XLA Ops": ops})]


def test_fail_self_time_per_step():
    """A while loop (0-600 ns) holds two transition ops, the chaos mask
    refresh and a rates op; the window [100, 1100) clips the first
    transition op, and the one after the loop counts whole:
    (200 - 100) + 100 + 300 = 500 ns over 5 steps.  The refresh, though
    in ``chaos``, is not a transition and does not count."""
    ops = [("%while.1", 0, 600, "jit(f)/while/body/chaos/cond"),
           ("%fusion.1", 50, 200, FAIL),
           ("%fusion.2", 300, 400, FAIL),
           ("%fusion.3", 400, 450, "jit(f)/while/body/chaos/vmap()/le"),
           ("%fusion.4", 450, 500, "jit(f)/while/body/rates/mul"),
           ("%fusion.5", 700, 1000, FAIL),
           ("%fusion.6", 1200, 1300, FAIL)]
    assert reader.fail_us(_planes(ops), 5) == pytest.approx(0.1)


def test_window_steps_in_proportion_to_chunks():
    """Three chunk spans of a unit of 4 chunks and 400 steps start inside
    the window [100, 1100); the fourth starts after it: 300 steps."""
    host = ("/host:CPU", {"main": [
        (trace.WINDOW, 100, 1100), ("repro.fleet.chunk", 100, 300),
        ("repro.fleet.chunk", 400, 600), ("repro.fleet.chunk", 900, 1200),
        ("repro.fleet.chunk", 1100, 1300)]})
    planes = [host, ("/device:TPU:0", {"XLA Ops": []})]
    assert reader.window_steps(planes, 400, 4) == pytest.approx(300)
    assert reader.window_steps(planes, 400, 0) == 0


def test_none_without_the_scope_or_steps():
    ops = [("%fusion.1", 200, 300, "jit(f)/while/body/chaos/and")]
    assert reader.fail_us(_planes(ops), 5) is None
    assert reader.fail_us(_planes([("%f", 200, 300, FAIL)]), 0) is None


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """Protobuf wire bytes of ``(number, int | bytes | str)`` fields."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_names_by_event_metadata_id(tmp_path):
    """Two programs both name an instruction ``%fusion.1``: each event
    gets the op_name of its own metadata entry (one a ``str_value``, one a
    ``ref_value`` to a stat metadata's name), not the first of its name."""
    fail_op = "jit(chunk)/while/body/vmap(chaos)/cond/branch_1_fun/" \
              "fail_transitions/add"
    stat_md = [_msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))),
               _msg((1, 8), (2, _msg((1, 8), (2, "jit(f)/rates/mul"))))]
    ev_md = [_msg((1, 1), (2, _msg((1, 1), (2, "%fusion.1"),
                                   (5, _msg((1, 7), (5, fail_op)))))),
             _msg((1, 2), (2, _msg((1, 2), (2, "%fusion.1"),
                                   (5, _msg((1, 7), (7, 8))))))]
    line = _msg((2, "XLA Ops"), (3, 1000),
                *[(4, _msg((1, i), (2, 100 * i), (3, 50))) for i in (1, 2, 1)])
    plane = _msg((2, "/device:TPU:0"), (3, line),
                 *[(4, m) for m in ev_md], *[(5, m) for m in stat_md])
    host = _msg((2, "/host:CPU"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, plane)))
    assert reader.event_op_names(str(path)) == {
        "/device:TPU:0": [fail_op, "jit(f)/rates/mul", fail_op]}
    (dev,) = [lines["XLA Ops"] for p, lines in reader.load_planes(str(path))
              if p == "/device:TPU:0"]
    assert [(e[0], e[3]) for e in dev] == [
        ("%fusion.1", fail_op), ("%fusion.1", "jit(f)/rates/mul"),
        ("%fusion.1", fail_op)]
