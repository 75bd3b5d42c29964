"""What the program records for a profiler (README "Tracing a run"):
``repro.*`` host spans at the front door, the runner dispatch and the
fleet's chunk boundaries; the ``FleetStats`` transfer counters; and the
``jax.named_scope`` phases of the engine step, which XLA keeps in every
fused operation's ``op_name``."""
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from repro.api import Experiment, PolicyConfig, fleet, runners
from repro.core import engine
from repro.core.policies import as_policy_arrays
from repro.scenarios import get_scenario

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
from harness import trace  # noqa: E402
from harness.program_trace import op_phase  # noqa: E402

PHASES = ("admit_place", "activate", "chaos", "rates", "advance", "complete")
FRONT = ("repro.front.grid", "repro.front.setup", "repro.front.routes",
         "repro.front.consts", "repro.front.policies")
FLEET = ("repro.fleet.cohort", "repro.fleet.chunk", "repro.fleet.sync",
         "repro.fleet.retire", "repro.fleet.refill")
POLICIES = [("legacy", PolicyConfig(routing=0)),
            ("sdn", PolicyConfig(routing=1))]


def _scenario():
    """A Scenario object (not a registry name), so every Experiment
    builds it and its consts anew."""
    return get_scenario("paper-fabric", n_each=1, split=2, k_max=16)


def _fleet_exp():
    # 2 routings (2 cohorts) x 3 seeds through 2 lanes: each refills
    return Experiment(scenarios=_scenario(), policies=POLICIES,
                      seeds=range(3))


def _within(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One ``Experiment.run`` and one small ``run_fleet`` under
    ``jax.profiler.trace`` (each warmed first, so nothing compiles in the
    trace), read back with the benchmark's ``trace.load_planes``."""
    np.asarray(Experiment(scenarios=_scenario(),
                          policies=POLICIES).run().states.steps)
    _fleet_exp().run_fleet(width=2, chunk_steps=8)
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        with TraceAnnotation("test.run"):
            exp = Experiment(scenarios=_scenario(), policies=POLICIES)
            np.asarray(exp.run().states.steps)
        with TraceAnnotation("test.fleet"):
            _, stats = _fleet_exp().run_fleet(width=2, chunk_steps=8,
                                              return_stats=True)
    planes = trace.load_planes(trace.find_xplane(str(out)))
    spans = {}
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for evs in lines.values():
                for n, a, b in evs:
                    spans.setdefault(n, []).append((a, b))
    return spans, stats


def test_front_door_and_dispatch_spans(traced):
    spans, _ = traced
    (run,) = spans["test.run"]
    (fl,) = spans["test.fleet"]
    for name in FRONT:
        assert spans.get(name), f"{name} not on a host plane"
    # one grid, Scenario.build, route table, consts and policy arrays
    # build per Experiment
    for name in FRONT:
        assert sum(_within(s, run) for s in spans[name]) == 1, name
        assert sum(_within(s, fl) for s in spans[name]) == 1, name
    # routes nest in setup, setup in grid; then consts, policies and the
    # dispatch follow each other
    (dispatch,) = spans["repro.run.dispatch"]
    assert _within(dispatch, run)
    grid, setup, routes, consts, policies = (
        next(s for s in spans[name] if _within(s, run)) for name in FRONT)
    assert _within(routes, setup) and _within(setup, grid)
    assert grid[1] <= consts[0] and consts[1] <= policies[0]
    assert policies[1] <= dispatch[0]


def test_fleet_boundary_spans(traced):
    spans, stats = traced
    (fl,) = spans["test.fleet"]
    for name in FLEET:
        assert spans.get(name), f"{name} not on a host plane"
        assert all(_within(s, fl) for s in spans[name]), name
    # one chunk, sync and retire span per chunk; one refill span per
    # boundary that refilled; a cohort span for the grouping, per
    # scenario and per cohort
    for name in ("repro.fleet.chunk", "repro.fleet.sync",
                 "repro.fleet.retire"):
        assert len(spans[name]) == stats.chunks, name
    assert 0 < len(spans["repro.fleet.refill"]) <= stats.refills
    assert len(spans["repro.fleet.cohort"]) == 2 + stats.cohorts == 4
    # the boundary spans follow each other; none nests in another
    seq = sorted(s for n in FLEET for s in spans[n])
    assert all(a[1] <= b[0] for a, b in zip(seq, seq[1:]))


class _Counted:
    """An independent count of host <-> device transfers: proxies for the
    fleet module's ``np`` and ``jnp`` that see every conversion, and
    wrappers for its programs that see every numpy argument."""

    def __init__(self, monkeypatch):
        self.d2h = self.d2h_bytes = self.h2d = self.h2d_bytes = 0
        counter = self

        class NumPy:
            def __getattr__(self, k):
                return getattr(np, k)

            def asarray(self, a, *args, **kw):
                if isinstance(a, jax.Array):
                    counter.d2h += 1
                    counter.d2h_bytes += a.nbytes
                return np.asarray(a, *args, **kw)

        class JaxNumPy:
            def __getattr__(self, k):
                return getattr(jnp, k)

            def asarray(self, a, *args, **kw):
                counter.host_args(a)
                return jnp.asarray(a, *args, **kw)

        monkeypatch.setattr(fleet, "np", NumPy())
        monkeypatch.setattr(fleet, "jnp", JaxNumPy())
        for name in ("_chunk_program", "_init_program", "_refill_program"):
            monkeypatch.setattr(fleet, name, self.wrap(getattr(fleet, name)))

    def host_args(self, *args):
        for a in jax.tree_util.tree_leaves(args):
            if isinstance(a, np.ndarray):
                self.h2d += 1
                self.h2d_bytes += a.nbytes

    def wrap(self, get_program):
        def get(*a, **kw):
            prog = get_program(*a, **kw)

            def call(*args):
                self.host_args(*args)
                return prog(*args)
            return call
        return get


def test_fleet_transfer_counters_match_an_independent_count(monkeypatch):
    exp = _fleet_exp()
    exp.build()
    exp.policy_arrays()
    counted = _Counted(monkeypatch)
    _, stats = exp.run_fleet(width=2, chunk_steps=8, return_stats=True)
    assert stats.cohorts == 2 and stats.refills > 0
    assert (stats.d2h, stats.d2h_bytes, stats.h2d, stats.h2d_bytes) == (
        counted.d2h, counted.d2h_bytes, counted.h2d, counted.h2d_bytes)
    # at least a done flag down and the lane policy rows up per chunk
    n_rows = len(exp.policy_arrays()) - len(fleet.STATIC_FIELDS)
    assert stats.d2h > stats.chunks
    assert stats.h2d >= stats.chunks * n_rows + stats.cohorts


def test_transfer_counters_count_bytes_from_shapes():
    st = fleet.FleetStats()
    st.fetch(jnp.zeros((3, 5), jnp.int32))
    st.fetch(np.zeros(7))                     # already on the host
    st.upload(np.zeros(4, bool))
    st.count_uploads({"a": np.zeros((2, 2), np.float32),
                      "b": jnp.zeros(9), "c": None})
    assert (st.d2h, st.d2h_bytes, st.h2d, st.h2d_bytes) == (1, 60, 2, 20)


# ---------------------------------------------------------------------------
# engine phase scopes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["paper-fabric", "paper-fabric-failures",
                                  "paper-fabric-ctrl", "paper-fabric-chaos"])
def test_every_step_statement_is_in_one_phase(name):
    """Each top-level equation of ``_step`` (everything nested in one
    inherits its scope) names exactly one phase, under every static
    feature switch the scenario turns on."""
    consts, meta = Experiment(scenarios=name).build()
    pol = as_policy_arrays(PolicyConfig())
    aux = engine._make_aux(consts, pol)
    s0 = engine.init_state_from_consts(consts, meta.n_switches,
                                       meta.ctrl_slots, meta.spec_slots)
    cache0 = {**engine._endpoint_cache(consts, meta, s0),
              "nc": jnp.zeros(meta.n_links, jnp.int32)}
    closed = jax.make_jaxpr(lambda c, p, a, sc: engine._step(
        c, meta, p, a, sc))(consts, pol, aux, (s0, cache0))
    seen = set()
    for eqn in closed.jaxpr.eqns:
        parts = str(eqn.source_info.name_stack).split("/")
        named = [p for p in parts if p in PHASES]
        assert len(named) == 1 and parts[0] == named[0], \
            f"{eqn.primitive} at {eqn.source_info.name_stack!r}"
        seen.add(named[0])
    chaos = meta.has_failures or meta.has_degradation or meta.spec_slots
    assert seen == set(PHASES) - (set() if chaos else {"chaos"})


def _loop_fusions(hlo: str):
    """(computation, fusion, op_name) of every fusion in the while loops'
    bodies and conditions (and the conditionals inside them), reached
    from the entry computation."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[cur] = []
        elif cur and line.startswith("  "):
            comps[cur].append(line)
    entry = next(c for c in comps if f"ENTRY {c}" in hlo)
    todo = [c for line in comps[entry]
            for c in re.findall(r"body=(%[\w.\-]+)", line)]
    seen, out = set(), []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps.get(c, []):
            todo += re.findall(r"(?:body|condition|true_computation|"
                               r"false_computation)=(%[\w.\-]+)", line)
            for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                    line):
                todo += [x.strip() for x in group.split(",")]
            if " fusion(" in line:
                on = re.findall(r'op_name="([^"]*)"', line)
                out.append((c, line.split("=")[0].strip(),
                            on[0] if on else ""))
    return out


def _loop_control(op_name: str, freeze: str) -> bool:
    """The loop's own work, outside ``_step``: operations XLA made with no
    op_name, the vmapped loop's per-lane freeze (``body_pred``, or the
    fleet chunk's ``tree_select``: ``freeze``), and single primitives at
    the body's top level (the done flag, the step counter)."""
    if not op_name or "body_pred" in op_name or op_name.endswith("/while"):
        return True
    tail = re.split(r"/while/(?:body|cond)/", op_name)[-1]
    return "/" not in tail or tail == freeze


def _compiled_loops():
    exp = Experiment(scenarios=_scenario(), policies=POLICIES)
    consts, meta = exp.build()
    pols = exp.policy_arrays()
    fn, init = runners._make_fn(meta, "policy_batch", counted=False)
    s0 = jax.eval_shape(init, consts, pols)
    batch = jax.jit(fn).lower(consts, pols, s0).compile().as_text()
    # the program Experiment.run gets: traffic and placement closed over
    sig = runners.static_signature({**pols, **exp._static_policies()})
    fn, _ = runners._make_fn(meta, "policy_batch", counted=False, sig=sig)
    uniform = jax.jit(fn).lower(consts, runners._varying(pols, sig),
                                s0).compile().as_text()
    chunk = engine.make_fleet_chunk(meta, {"routing": 1, "traffic": 0,
                                           "placement": 0}, 8)
    lane = {k: np.asarray(v) for k, v in pols.items()
            if k not in fleet.STATIC_FIELDS}
    carry = jax.eval_shape(lambda c: engine.init_fleet_carry(c, meta, 2),
                           consts)
    fleet_hlo = jax.jit(chunk).lower(consts, lane, carry).compile().as_text()
    # the same programs with failures on: the serial runner, and the
    # chunk that decides the failure transitions' predicate outside its
    # lane vmap
    fexp = Experiment(scenarios=get_scenario(
        "paper-fabric-failures", n_each=1, split=2, k_max=16),
        policies=POLICIES)
    fconsts, fmeta = fexp.build()
    fn, init = runners._make_fn(fmeta, "single", counted=False)
    pol = as_policy_arrays(PolicyConfig())
    serial = jax.jit(fn).lower(fconsts, pol, jax.eval_shape(
        init, fconsts, pol)).compile().as_text()
    fchunk = engine.make_fleet_chunk(fmeta, {"routing": 1, "traffic": 0,
                                             "placement": 0}, 8)
    fcarry = jax.eval_shape(
        lambda c: engine.init_fleet_carry(c, fmeta, 2), fconsts)
    fleet_fail = jax.jit(fchunk).lower(fconsts, lane,
                                       fcarry).compile().as_text()
    freeze = "cond/branch_1_fun/jit(_where)/select_n"
    return {"policy_batch": (batch, None),
            "policy_batch_uniform": (uniform, None),
            "fleet_chunk": (fleet_hlo, freeze),
            "serial_failures": (serial, None),
            "fleet_chunk_failures": (fleet_fail, freeze)}


@pytest.fixture(scope="module")
def compiled_loops():
    return _compiled_loops()


@pytest.mark.parametrize("program", ["policy_batch", "policy_batch_uniform",
                                     "fleet_chunk", "fleet_chunk_failures"])
def test_every_loop_fusion_names_a_phase(compiled_loops, program):
    hlo, freeze = compiled_loops[program]
    fusions = _loop_fusions(hlo)
    phases = {op_phase(on) for _, _, on in fusions}
    assert set(PHASES) - {"chaos"} <= phases
    stray = [(c, f, on) for c, f, on in fusions
             if not op_phase(on) and not _loop_control(on, freeze)]
    assert not stray, stray


def test_uniform_eq3_batch_has_no_waterfill_loop(compiled_loops):
    """With traffic closed over as Eq. 3, the water-fill fill loop (a
    ``while`` of scatter-adds under ``rates``) is gone from the batch
    program; the generic program runs it under the batched select."""
    def rates_ops(hlo, op):
        return [line for line in hlo.splitlines()
                if f" {op}(" in line and "/rates/" in line]

    generic, _ = compiled_loops["policy_batch"]
    uniform, _ = compiled_loops["policy_batch_uniform"]
    assert len(rates_ops(generic, "while")) == 1
    assert rates_ops(uniform, "while") == []
    assert "/rates/" not in "".join(
        line for line in uniform.splitlines() if "scatter" in line)


@pytest.mark.parametrize("program", ["policy_batch", "policy_batch_uniform",
                                     "fleet_chunk", "fleet_chunk_failures"])
def test_route_choice_sits_under_activate(compiled_loops, program):
    """Every operation of the route choice and route-link composition
    (``route_choice``) is nested in the ``activate`` phase, so the phase
    readers keep counting it there."""
    hlo, _ = compiled_loops[program]
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    route = [n for n in names if "route_choice" in n]
    assert route
    for n in route:
        assert op_phase(n) == "activate", n
        parts = n.split("/")
        at = next(i for i, p in enumerate(parts) if "route_choice" in p)
        assert any("activate" in p for p in parts[:at]), n


@pytest.mark.parametrize("program, chaos", [
    ("serial_failures", "chaos"), ("fleet_chunk_failures", r"vmap\(chaos\)")])
def test_fail_transitions_sit_under_chaos(compiled_loops, program, chaos):
    """The failure transitions (``fail_transitions``) are nested in the
    ``chaos`` phase, in a conditional's branch: in the serial runner and
    in the fleet chunk, whose predicate comes from outside its lane vmap
    so the cond survives the vmap (``vmap(chaos)/cond``)."""
    hlo, _ = compiled_loops[program]
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    fail = [n for n in names if "fail_transitions" in n]
    assert fail
    inside = re.compile(r"/" + chaos
                        + r"/cond/branch_\d+_fun/fail_transitions/")
    for n in fail:
        assert op_phase(n) == "chaos", n
        assert inside.search(n), n


@pytest.mark.parametrize("k_max, truncated", [(8, 48), (16, 0)])
def test_route_table_counters_on_the_paper_fabric(k_max, truncated):
    """The Fig. 9 fabric: 8 edge switches and core 1 (the SAN's) are the
    attachments.  Per ordered pair of edges in different pods 2 aggs x 2
    cores x 2 x 2 parallel cables = 16 routes (48 pairs), in one pod 2
    (8 pairs); core 1 to an edge and back 2 each (16 pairs)."""
    from repro.core.routing import build_route_table
    from repro.core.topology import paper_fat_tree
    topo = paper_fat_tree()
    rt = build_route_table(topo, k_max=k_max)
    assert rt.n_pairs == 9 * 9
    assert rt.n_truncated == truncated
    assert rt.n_enumerated == (48 * min(16, k_max) + 8 * 2 + 16 * 2)
    h = rt.max_hops
    assert h == 6
    assert rt.device_bytes == (4 * 81 * k_max * h + 2 * 4 * 81
                               + 3 * 4 * topo.n_nodes)
