"""The ``step_route_us.ft16`` reader on hand-made planes: self time of the
operations under the engine's ``route_choice`` scope, inside the window,
per engine step."""
import importlib.util

import pytest

from harness import core, trace

spec = importlib.util.spec_from_file_location(
    "route_reader", core.BENCH / "metrics" / "step_route_us.ft16.py")
reader = importlib.util.module_from_spec(spec)
spec.loader.exec_module(reader)

ROUTE = "jit(chunk)/while/body/vmap(activate)/route_choice/gather"


@pytest.mark.parametrize("op_name,inside", [
    (ROUTE, True),
    ("jit(f)/while/body/activate/vmap(route_choice)/min", True),
    ("jit(f)/while/body/activate/while", False),
    ("jit(f)/while/body/activate/route_choices/add", False),
    ("", False),
])
def test_in_scope(op_name, inside):
    assert reader.in_scope(op_name) is inside


def _planes(ops):
    host = ("/host:CPU", {"main": [(trace.WINDOW, 100, 1100)]})
    return [host, ("/device:TPU:0", {"XLA Ops": ops})]


def test_route_self_time_per_step():
    """A while loop (0-600 ns) holds two route ops and one other; the
    window [100, 1100) clips the first route op, and the route op after
    it counts whole: (200 - 100) + 100 + 300 = 500 ns over 5 steps."""
    ops = [("%while.1", 0, 600, "jit(f)/while/body/activate/while"),
           ("%fusion.1", 50, 200, ROUTE),
           ("%fusion.2", 300, 400, ROUTE),
           ("%fusion.3", 400, 500, "jit(f)/while/body/rates/mul"),
           ("%fusion.4", 700, 1000, ROUTE),
           ("%fusion.5", 1200, 1300, ROUTE)]
    assert reader.route_us(_planes(ops), 5) == pytest.approx(0.1)


def test_none_without_the_scope_or_steps():
    ops = [("%fusion.1", 200, 300, "jit(f)/while/body/activate/add")]
    assert reader.route_us(_planes(ops), 5) is None
    assert reader.route_us(_planes([("%f", 200, 300, ROUTE)]), 0) is None
