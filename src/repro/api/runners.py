"""Compiled-runner cache: one traced engine program per (SimMeta, batch
kind, static signature), shared by every entry point (DESIGN.md §6).

``simulate`` used to rebuild ``jax.jit(make_simulator(setup))`` on every
call, throwing the trace away each time.  Here the jitted callable is cached
under the run's hashable ``SimMeta``, the batch kind and the signature
below, so a second run with an equal meta (and equal tensor shapes —
jax.jit keys on those) reuses the compiled program with ZERO retraces.
``trace_count()`` exposes the number of engine traces for
tests/benchmarks to assert exactly that.

Batch kinds (all funnel into ``make_packed_simulator``'s ``run(consts,
pol)``):

==============  =============================  ==========================
kind            consts                         policies
==============  =============================  ==========================
"single"        unbatched                      unbatched dict
"policy_batch"  unbatched (broadcast)          leading policy dim [P]
"zipped"        leading replica dim [R]        leading replica dim [R]
"grid"          leading scenario dim [S]       leading policy dim [P]
==============  =============================  ==========================

"grid" nests the vmaps (scenarios outer, policies inner) so the dense
consts tensors broadcast across the policy axis instead of being
materialized P times (DESIGN.md §5).

The t=0 state is built by a separate (cached, jitted) initializer and
passed into the main program as a DONATED argument (DESIGN.md §8): XLA
aliases the init buffers straight into the while-loop carry and the final
``SimState`` outputs instead of materializing a second copy per replica.
Every backend donates, the CPU included, so the CPU tests exercise the
same buffer lifetimes as the TPU.

The batched kinds close over uniform branch fields by the fleet's rule
(DESIGN.md §6, §9): a ``STATIC_FIELDS`` entry of ``pols`` given as one
host int for the whole batch (``Experiment.run`` passes those its
policies hold uniform) is closed over as a Python int, so the engine picks
that branch at trace time — under ``vmap`` a ``lax.cond`` on a batched
predicate runs both branches.  A ``[P]`` column counts as varying: the
signature is read from host values only, never fetched.  The program
cache key is ``(meta, kind, sig)``, ``sig`` giving each static field's
value or ``None`` where it varies; an all-``None`` sig is the generic
program.  ``dispatch_counts()`` counts the batched calls by program.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Dict, Mapping, Optional, Tuple

import jax

from ..core.engine import (init_state_from_consts, make_packed_simulator,
                           static_policy_value)
from ..core.simmeta import SimMeta

KINDS = ("single", "policy_batch", "zipped", "grid")

# the branch-selecting policy axes: closed over as Python ints wherever a
# batch holds one value of them (fleet cohorts, uniform batched calls)
STATIC_FIELDS = ("routing", "traffic", "placement")
Sig = Tuple[Optional[int], ...]
GENERIC: Sig = (None,) * len(STATIC_FIELDS)

# LRU-bounded: each entry retains a jitted callable plus its compiled XLA
# executables, and callers like roofline/advisor produce a fresh SimMeta per
# candidate schedule — without eviction a long-running process would leak
# one executable per shape ever seen.
CACHE_MAX = 64
_CACHE: "OrderedDict[Tuple, Callable]" = OrderedDict()
_TRACE_COUNT = 0
_DISPATCH = {"specialised": 0, "generic": 0}


def trace_count() -> int:
    """Total engine traces since import (or the last ``cache_clear``)."""
    return _TRACE_COUNT


def dispatch_counts() -> Dict[str, int]:
    """Batched runner calls since import (or the last ``cache_clear``), by
    the program they got: ``specialised`` (some static field closed over)
    or ``generic``."""
    return dict(_DISPATCH)


def cache_size() -> int:
    return len(_CACHE)


def cache_clear() -> None:
    """Drop all cached programs and reset the trace and dispatch counters
    (tests)."""
    global _TRACE_COUNT
    _CACHE.clear()
    _TRACE_COUNT = 0
    _DISPATCH.update(specialised=0, generic=0)


def get_cached_program(key: Tuple, builder: Callable[[], Callable]) -> Callable:
    """The shared program cache: ``builder()`` runs at most once per ``key``
    (hashable tuple), its result LRU-retained up to ``CACHE_MAX`` entries.
    ``get_runner`` and the fleet layer (``api.fleet``, DESIGN.md §9) both
    park their jitted chunk/runner programs here, so one ``cache_clear``
    resets everything tests care about."""
    if key not in _CACHE:
        _CACHE[key] = builder()
        while len(_CACHE) > CACHE_MAX:
            _CACHE.popitem(last=False)
    _CACHE.move_to_end(key)
    return _CACHE[key]


def note_trace() -> None:
    """Bump the trace counter — called at TRACE time from inside a traced
    function, so jit-cache hits don't count (see ``_build.counted``)."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def get_runner(meta: SimMeta, kind: str) -> Callable:
    """The jitted ``run(consts, pols) -> SimState`` for this meta.

    Calling it with tensor shapes it has already seen is trace-free; new
    shapes (e.g. a different job count under the same meta) trace once and
    are cached by jit itself.  A batched kind dispatches each call to the
    cached program of its host-static signature (``static_signature``).
    """
    meta = SimMeta.coerce(meta)
    if kind not in KINDS:
        raise ValueError(f"unknown runner kind {kind!r}; one of {KINDS}")
    if kind == "single":
        return _program(meta, kind, GENERIC)

    def call(consts, pols):
        sig = static_signature(pols)
        _DISPATCH["generic" if sig == GENERIC else "specialised"] += 1
        return _program(meta, kind, sig)(consts, _varying(pols, sig))

    return call


def static_signature(pols: Mapping) -> Sig:
    """Per ``STATIC_FIELDS`` entry: its value where ``pols`` gives it as
    one host int for the whole batch, else ``None`` — a ``[P]`` column, on
    the device or not, counts as varying (reading a device one would add a
    device-to-host sync per call)."""
    return tuple(static_policy_value(pols.get(f)) for f in STATIC_FIELDS)


def _varying(pols: Mapping, sig: Sig) -> Dict:
    """``pols`` without the fields ``sig`` closes over."""
    static = {f for f, v in zip(STATIC_FIELDS, sig) if v is not None}
    return {k: v for k, v in pols.items() if k not in static}


def _program(meta: SimMeta, kind: str, sig: Sig) -> Callable:
    return get_cached_program((meta, kind, sig),
                              lambda: _build(meta, kind, sig))


# The donation policy shared by every jitted engine program (here and
# ``api.fleet._chunk_program``), on every backend alike: argument 2 — the
# t=0 state / chunk carry — is donated so XLA aliases the init buffers
# straight into the while-loop carry and final outputs.  A donated buffer
# is deleted by the call, so no caller may read it again.  Audited by the
# static analyzer (jaxcheck:donation, DESIGN.md §12).
DONATE_ARGNUMS: Tuple[int, ...] = (2,)


def traced_jaxpr(meta: SimMeta, kind: str, consts, pols):
    """Static-analysis hook (DESIGN.md §12): the engine program exactly as
    ``get_runner`` would jit it, traced to a ClosedJaxpr without
    compiling, plus the number of trailing flat inputs that belong to the
    donated t=0 state argument.  Neither the program cache nor the trace
    counter is touched — ``trace_count()`` assertions stay exact."""
    meta = SimMeta.coerce(meta)
    if kind not in KINDS:
        raise ValueError(f"unknown runner kind {kind!r}; one of {KINDS}")
    sig = GENERIC if kind == "single" else static_signature(pols)
    pols = _varying(pols, sig)
    fn, init = _make_fn(meta, kind, counted=False, sig=sig)
    s0 = jax.eval_shape(init, consts, pols)
    closed = jax.make_jaxpr(fn)(consts, pols, s0)
    return closed, len(jax.tree_util.tree_leaves(s0))


def _make_fn(meta: SimMeta, kind: str, counted: bool = True,
             sig: Sig = GENERIC):
    """(run_fn, init_fn) for one batch kind, before jit — shared by the
    runner cache (``_build``) and the analysis hook (``traced_jaxpr``).
    ``sig`` names the static fields closed over; the functions take the
    policies without them."""
    base = make_packed_simulator(meta)
    static_pol = {f: v for f, v in zip(STATIC_FIELDS, sig) if v is not None}

    def counted_fn(consts, pol, s0):
        # executes at TRACE time only — the compiled program has no trace
        # of it, so the counter counts traces, not runs.
        if counted:
            note_trace()
        return base(consts, {**pol, **static_pol}, s0)

    def init_one(consts, pol):
        del pol  # the t=0 state depends on consts only; pol carries the
        #          batch axes the vmapped variants map over
        return init_state_from_consts(consts, meta.n_switches,
                                      meta.ctrl_slots, meta.spec_slots)

    if kind == "single":
        fn, init = counted_fn, init_one
    elif kind == "policy_batch":
        fn = jax.vmap(counted_fn, in_axes=(None, 0, 0))
        init = jax.vmap(init_one, in_axes=(None, 0))
    elif kind == "zipped":
        fn = jax.vmap(counted_fn)
        init = jax.vmap(init_one)
    else:  # grid: scenarios outer, policies inner
        def fn(consts, pols, s0):
            return jax.vmap(lambda c, s0c: jax.vmap(
                lambda p, s0p: counted_fn(c, p, s0p))(pols, s0c))(consts, s0)

        def init(consts, pols):
            return jax.vmap(lambda c: jax.vmap(
                lambda p: init_one(c, p))(pols))(consts)

    return fn, init


def _build(meta: SimMeta, kind: str, sig: Sig) -> Callable:
    fn, init = _make_fn(meta, kind, sig=sig)
    run_jit = jax.jit(fn, donate_argnums=DONATE_ARGNUMS)
    init_jit = jax.jit(init)

    def call(consts, pols):
        return run_jit(consts, pols, init_jit(consts, pols))

    return call
