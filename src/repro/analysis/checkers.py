"""Jaxpr-level checkers over traced engine programs (DESIGN.md §12).

Each checker takes a ``ProgramTrace`` (the traced program plus the axis
sizes and donation info needed to interpret it) and returns ``Finding``s;
``budget_counts`` extracts the per-program primitive counts and carry
signature that land in ``experiments/PRIM_BUDGET.json``.  ``analyze``
drives all of it over a sweep of traces, including the cross-program
carry-stability check (jaxcheck:carry-stability).

The checkers deliberately operate on *structure*, not source: a sort
that sneaks back into the hot loop trips jaxcheck:sort-in-loop no matter
which file introduced it, with the offending eqn's source location in
the finding.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Dict, List, Optional, Sequence, Tuple

from .jaxpr_walk import (LoopInfo, aval_sig, carry_signature, engine_loop,
                         source_of, walk)
from .rules import Finding

SCATTER_PRIMS = ("scatter", "scatter-add", "scatter-mul", "scatter-min",
                 "scatter-max")

# budgeted primitives: counted inside the engine loop body per program.
# An INCREASE over the committed baseline fails the gate for every prim
# except "cond", where a DECREASE fails instead — losing a lax.cond means
# an unbatched fast path became a both-branches select (the batch wall,
# jaxcheck:batched-cond).
WATCHED = ("sort",) + SCATTER_PRIMS + (
    "gather", "select_n", "cond", "while", "scan",
    "convert_element_type", "dynamic_update_slice", "dynamic_slice")


@dataclasses.dataclass
class ProgramTrace:
    """One traced engine program plus the context checkers need."""
    key: str                    # ledger key, e.g. "paper-fabric/serial"
    kind: str                   # "serial" | "fleet" | "refill" | "doctored"
    scenario: str
    meta: object                # hashable SimMeta (or a test sentinel)
    closed: object              # ClosedJaxpr
    axes: Dict[str, int]        # {"packets": n, "tasks": n, "jobs": n, ...}
    sig: Optional[Tuple[int, ...]] = None   # fleet static signature
    donated: int = 0            # trailing flat invars that form the
    #                             donated state arg
    expect_loop: bool = True    # engine programs must contain a while
    expect_loop_cond: bool = True  # ... whose body keeps >=1 lax.cond


def loop_of(trace: ProgramTrace) -> Optional[LoopInfo]:
    return engine_loop(trace.closed)


def _where(trace: ProgramTrace, path, eqn) -> str:
    return f"{trace.key} @ {'/'.join(path)} [{source_of(eqn)}]"


# --- jaxcheck:sort-in-loop / jaxcheck:scatter-in-loop ---------------------

def check_forbidden(trace: ProgramTrace,
                    loop: Optional[LoopInfo]) -> List[Finding]:
    """Packet-axis sorts and full-width packet-axis scatters in the loop
    body.  The job/vm/task-axis sorts and the single-element pops /
    link segment-sums the vectorized kernel keeps on purpose do NOT
    match: they are caught by the budget counts instead."""
    if loop is None:
        return []
    n_pkt = trace.axes.get("packets", -1)
    out: List[Finding] = []
    for eqn, path in walk(loop.body):
        name = eqn.primitive.name
        if name == "sort":
            if any(n_pkt in tuple(v.aval.shape) for v in eqn.invars
                   if hasattr(v, "aval")):
                out.append(Finding(
                    rule="sort-in-loop",
                    where=_where(trace, path, eqn),
                    message=f"sort over the packet axis (n={n_pkt}) "
                            "inside the engine loop body",
                    key=f"sort-in-loop:{trace.key}"))
        elif name in SCATTER_PRIMS:
            # operands: (operand, indices, updates); full-width means the
            # UPDATES tensor spans the whole packet axis
            if len(eqn.invars) >= 3 and hasattr(eqn.invars[2], "aval"):
                upd = tuple(eqn.invars[2].aval.shape)
                if n_pkt in upd:
                    out.append(Finding(
                        rule="scatter-in-loop",
                        where=_where(trace, path, eqn),
                        message=f"{name} with full packet-axis updates "
                                f"{upd} inside the engine loop body",
                        key=f"scatter-in-loop:{trace.key}"))
    return out


# --- jaxcheck:dtype-drift -------------------------------------------------

def _is_widening(src_dtype, dst_dtype) -> bool:
    import numpy as np
    s, d = np.dtype(src_dtype), np.dtype(dst_dtype)
    same_kind = (s.kind == d.kind) or (s.kind in "iu" and d.kind in "iu")
    return same_kind and s.kind != "b" and d.itemsize > s.itemsize


def check_dtype_drift(trace: ProgramTrace,
                      loop: Optional[LoopInfo]) -> List[Finding]:
    """64-bit carry leaves and widening ``convert_element_type`` eqns in
    the loop body (whole program when there is no loop, e.g. refill)."""
    out: List[Finding] = []
    if loop is not None:
        for i, aval in enumerate(loop.carry_avals):
            shape, dtype = aval_sig(aval)
            if dtype.endswith("64") or dtype == "complex128":
                out.append(Finding(
                    rule="dtype-drift",
                    where=f"{trace.key} @ carry[{i}]",
                    message=f"{dtype} leaf {shape} in the loop carry",
                    key=f"dtype-drift:{trace.key}:carry"))
    body = loop.body if loop is not None else trace.closed.jaxpr
    for eqn, path in walk(body):
        if eqn.primitive.name != "convert_element_type":
            continue
        if not (eqn.invars and hasattr(eqn.invars[0], "aval")):
            continue
        src = eqn.invars[0].aval.dtype
        dst = eqn.params.get("new_dtype")
        if dst is not None and _is_widening(src, dst):
            out.append(Finding(
                rule="dtype-drift",
                where=_where(trace, path, eqn),
                message=f"widening convert {src} -> {dst} in the "
                        "engine loop body",
                key=f"dtype-drift:{trace.key}:{src}->{dst}"))
    return out


# --- jaxcheck:batched-cond ------------------------------------------------

def check_batched_cond(trace: ProgramTrace,
                       loop: Optional[LoopInfo]) -> List[Finding]:
    """Under vmap, a ``lax.cond`` with a batched predicate disappears —
    both branches run and a ``select_n`` merges them.  The serial kernel
    and the fleet chunk both keep at least one REAL cond (the per-step
    done-skip / cohort freeze fast path); a loop body with zero conds
    means every fast path got batched away.  Count drifts smaller than
    zero-vs-some are caught by the budget's cond/select_n entries."""
    if loop is None or not trace.expect_loop_cond:
        return []
    n_cond = sum(1 for eqn, _ in walk(loop.body)
                 if eqn.primitive.name == "cond")
    if n_cond == 0:
        return [Finding(
            rule="batched-cond",
            where=f"{trace.key} @ {'/'.join(loop.path)}",
            message="engine loop body contains no lax.cond at all — the "
                    "unbatched fast paths have been batched into "
                    "both-branches select_n",
            key=f"batched-cond:{trace.key}")]
    return []


# --- jaxcheck:donation ----------------------------------------------------

def check_donation(trace: ProgramTrace) -> List[Finding]:
    """Aval feasibility of buffer donation: every donated input must find
    a distinct output aval of the same shape/dtype to alias into,
    otherwise XLA silently keeps both copies and the donation is a lie.
    (The policy itself — donate the state argument — is checked once
    per run by ``check_donation_policy``.)"""
    if trace.donated <= 0:
        return []
    jaxpr = trace.closed.jaxpr
    donated = [v.aval for v in jaxpr.invars[-trace.donated:]]
    outs = Counter(aval_sig(v.aval) for v in jaxpr.outvars
                   if hasattr(v, "aval"))
    missing = []
    for a in donated:
        sig = aval_sig(a)
        if outs[sig] > 0:
            outs[sig] -= 1
        else:
            missing.append(sig)
    if missing:
        return [Finding(
            rule="donation",
            where=f"{trace.key} @ invars[-{trace.donated}:]",
            message=f"{len(missing)} donated input aval(s) have no "
                    f"matching output to alias into, e.g. {missing[0]}",
            key=f"donation:{trace.key}")]
    return []


def check_donation_policy(donate_argnums) -> List[Finding]:
    """The single-source-of-truth donation policy used by the runner
    cache and the fleet chunk: argument 2 (the t=0 state) is donated, on
    every backend alike."""
    got = tuple(donate_argnums)
    if got == (2,):
        return []
    return [Finding(
        rule="donation",
        where="runners.DONATE_ARGNUMS",
        message=f"expected donate_argnums (2,), got {got}",
        key="donation:policy")]


# --- jaxcheck:carry-stability ---------------------------------------------

def check_carry_stability(
        entries: Sequence[Tuple[ProgramTrace, Optional[LoopInfo]]],
) -> List[Finding]:
    """Programs sharing a (SimMeta, kind) must agree on the engine-loop
    carry structure — a scenario whose workload seed (not geometry)
    changed may never change the compiled program's carry."""
    groups: Dict[Tuple, Tuple[str, Tuple]] = {}
    out: List[Finding] = []
    for trace, loop in entries:
        if loop is None:
            continue
        leaves, nbytes, digest = carry_signature(loop.carry_avals)
        group = (trace.meta, trace.kind)
        prev = groups.get(group)
        if prev is None:
            groups[group] = (trace.key, (leaves, nbytes, digest))
        elif prev[1] != (leaves, nbytes, digest):
            out.append(Finding(
                rule="carry-stability",
                where=f"{trace.key} vs {prev[0]}",
                message=f"same SimMeta/kind but different loop carry: "
                        f"{(leaves, nbytes, digest)} vs {prev[1]}",
                key=f"carry-stability:{trace.kind}:{trace.scenario}"))
    return out


# --- budget extraction ----------------------------------------------------

def budget_counts(trace: ProgramTrace, loop: Optional[LoopInfo]) -> dict:
    """The committed-ledger row for one program: watched primitive counts
    inside the engine loop body (whole program when loop-free) plus the
    carry signature."""
    body = loop.body if loop is not None else trace.closed.jaxpr
    c: Counter = Counter()
    total = 0
    for eqn, _ in walk(body):
        total += 1
        name = eqn.primitive.name
        if name in WATCHED:
            c[name] += 1
    row = {"loop": {k: int(c.get(k, 0)) for k in WATCHED},
           "eqns": total}
    if loop is not None:
        leaves, nbytes, digest = carry_signature(loop.carry_avals)
        row["carry"] = {"leaves": leaves, "bytes": nbytes, "sig": digest}
    return row


def analyze(traces: Sequence[ProgramTrace]) -> Tuple[List[Finding], dict]:
    """Run every per-program checker plus the cross-program ones.
    Returns ``(findings, programs)`` where ``programs`` maps ledger key
    -> budget row."""
    findings: List[Finding] = []
    programs: dict = {}
    entries: List[Tuple[ProgramTrace, Optional[LoopInfo]]] = []
    for trace in traces:
        loop = loop_of(trace)
        entries.append((trace, loop))
        if trace.expect_loop and loop is None:
            findings.append(Finding(
                rule="carry-stability",
                where=trace.key,
                message="expected an engine while loop but the traced "
                        "program contains none",
                key=f"carry-stability:no-loop:{trace.key}"))
        findings += check_forbidden(trace, loop)
        findings += check_dtype_drift(trace, loop)
        findings += check_batched_cond(trace, loop)
        findings += check_donation(trace)
        programs[trace.key] = budget_counts(trace, loop)
    findings += check_carry_stability(entries)
    return findings, programs
