"""Fleet execution engine: chunked early-exit cohorts over the policy grid
(DESIGN.md §9).

The single vmapped ``lax.while_loop`` the runners use charges every lane for
the LONGEST trajectory in the batch, and its batched ``lax.cond`` policy
dispatch executes both branches of every policy — together the "batch wall"
that made width-6 vmap ~100x slower than serial.  The fleet layer cracks it
with three composed mechanisms:

1. **Chunked early-exit cohorts** — the grid drains through fixed-width
   cohorts of lanes advanced by K-step jitted chunks
   (``engine.make_fleet_chunk``).  Between chunks the host retires finished
   lanes, keeps their final state, and refills the lane from the pending
   queue, so no sim runs more than ``K - 1`` wasted events past its own
   finish.
2. **Bucketed admission** — a cheap calibrated step-count predictor
   (``StepPredictor``) orders the queue by expected trajectory length, so a
   cohort wave holds similar-length sims and the intra-chunk early exit
   (``jnp.all(done)``) actually fires.  Lanes are grouped by their STATIC
   policy signature (routing / traffic / placement) first: uniform branch
   fields are closed over as Python ints, letting the engine specialize its
   dispatch instead of paying for both branches under vmap.
3. **Device sharding** — with more than one visible device the lane axis
   runs under ``jax.shard_map`` over a 1-D ``"fleet"`` mesh
   (``launch.mesh``); each device drains its own slice of the cohort with
   no collectives (lanes are independent; the chunk's early exit is a
   shard-local ``jnp.all``).

Results are bit-identical to ``Experiment.run``'s serial/vmapped runners:
the chunk applies the SAME ``_step`` and freezes each lane at the first
state where ``_finished`` holds — exactly the state the serial while-loop
stops at (tests/test_fleet.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.engine import init_fleet_carry, make_fleet_chunk, tree_select
from ..core.simmeta import SimMeta
from . import runners
from .results import Results

# the branch-selecting policy axes (one definition, shared with the
# batched runners): uniform per cohort, closed over as Python ints so the
# engine's dispatch specializes at trace time
STATIC_FIELDS = runners.STATIC_FIELDS


class StepPredictor:
    """Cheap step-count predictor with online calibration (DESIGN.md §9).

    Admission order only needs RELATIVE lengths, so the model is minimal: a
    size prior ``alpha * (n_tasks + n_packets)`` (step count scales with
    how many completion/activation events the workload can generate),
    refined by an EWMA over observed final step counts keyed at two
    granularities — the (scenario, static-sig) group and the individual
    grid member.  Within a fresh group every member shares the group
    estimate (ordering is a no-op); on repeated fleets — benchmark reruns,
    advisor loops — member-level observations take over and genuinely
    length-divergent sims sort into the same cohort wave.
    """

    def __init__(self, alpha: float = 3.0, ewma: float = 0.4):
        self.alpha = alpha
        self.ewma = ewma
        self._obs: Dict[Hashable, float] = {}

    def predict(self, member_key: Hashable, group_key: Hashable,
                n_tasks: int, n_packets: int) -> float:
        prior = self.alpha * (n_tasks + n_packets)
        return self._obs.get(member_key,
                             self._obs.get(group_key, prior))

    def observe(self, key: Hashable, steps: float) -> None:
        cur = self._obs.get(key)
        self._obs[key] = (steps if cur is None
                          else (1 - self.ewma) * cur + self.ewma * steps)

    def clear(self) -> None:
        self._obs.clear()


# process-wide: calibration persists across fleets in one process
_PREDICTOR = StepPredictor()


class CohortSchedule:
    """Host-side retire/refill bookkeeping for one cohort of ``width``
    lanes draining ``members`` (already in admission order).

    Lanes hold a member id or ``None`` (a PAD lane: starts — and stays —
    done, so the chunk freezes it for free).  ``step(done)`` is called at
    every chunk boundary with the device's done flags; it retires finished
    lanes and refills them from the queue, returning what the driver must
    do on-device: extract the retired lanes' states BEFORE applying the
    refill mask (a refill overwrites the lane with the t=0 state).
    """

    def __init__(self, members: Sequence[Any], width: int):
        self.width = width
        self.queue: List[Any] = list(members)
        self.lane: List[Any] = [
            self.queue.pop(0) if self.queue else None for _ in range(width)]
        self.retired: List[Tuple[int, Any]] = []

    def pad_mask(self) -> np.ndarray:
        """[W] bool: lanes with no member — force their done flag at t=0."""
        return np.array([m is None for m in self.lane])

    @property
    def active(self) -> bool:
        return any(m is not None for m in self.lane)

    def step(self, done: np.ndarray) -> Tuple[List[Tuple[int, Any]],
                                              np.ndarray]:
        """-> (retire, refill_mask) for one chunk boundary.

        ``retire`` lists ``(lane, member)`` pairs whose final state must be
        extracted now; ``refill_mask`` marks lanes reassigned to the next
        queued member (reset them to the t=0 carry).  A finished lane with
        an empty queue becomes a pad lane.
        """
        retire: List[Tuple[int, Any]] = []
        refill = np.zeros(self.width, bool)
        for i in range(self.width):
            if done[i] and self.lane[i] is not None:
                retire.append((i, self.lane[i]))
                if self.queue:
                    self.lane[i] = self.queue.pop(0)
                    refill[i] = True
                else:
                    self.lane[i] = None
        self.retired.extend(retire)
        return retire, refill


@dataclasses.dataclass
class FleetStats:
    """What the fleet actually did — surfaced for benchmarks and tests."""

    sims: int = 0        # grid cells drained
    cohorts: int = 0     # (scenario × static-sig) groups
    chunks: int = 0      # K-step chunk invocations
    refills: int = 0     # lanes recycled mid-cohort
    devices: int = 1     # fleet-mesh size (1 = no shard_map)
    width: int = 0       # lanes per cohort (after device round-up)
    # engine steps the chunks ran (summed over devices), and how many of
    # them ran the failure transitions for some lane (DESIGN.md §9)
    chunk_steps: int = 0
    fail_steps: int = 0
    # every array run_fleet moves between host and device, and its bytes
    # (shape x itemsize): done flags, retire fetches, masks and the numpy
    # arguments a chunk, init or refill call uploads
    d2h: int = 0
    d2h_bytes: int = 0
    h2d: int = 0
    h2d_bytes: int = 0

    def fetch(self, a) -> np.ndarray:
        """``a`` on the host; a device array counts as one transfer."""
        if isinstance(a, jax.Array):
            self.d2h += 1
            self.d2h_bytes += a.nbytes
        return np.asarray(a)

    def upload(self, a: np.ndarray) -> jax.Array:
        """``a`` on the device, counted as one transfer."""
        self.count_uploads(a)
        return jnp.asarray(a)

    def count_uploads(self, tree) -> None:
        """Count the numpy leaves of ``tree``: a jitted call uploads each."""
        for a in jax.tree_util.tree_leaves(tree):
            if isinstance(a, np.ndarray):
                self.h2d += 1
                self.h2d_bytes += a.nbytes


def _chunk_program(meta: SimMeta, sig: Tuple[int, ...], chunk_steps: int,
                   width: int, n_dev: int) -> Callable:
    """The cached jitted (and, for ``n_dev > 1``, shard_mapped) chunk."""
    key = ("fleet", meta, sig, chunk_steps, width, n_dev)

    def build() -> Callable:
        static_pol = dict(zip(STATIC_FIELDS, sig))
        chunk = make_fleet_chunk(meta, static_pol, chunk_steps)

        def counted(consts, pol, carry):
            runners.note_trace()
            carry, counts = chunk(consts, pol, carry)
            return carry, counts[None]     # [devices, 2] across the mesh

        fn = counted
        if n_dev > 1:
            from jax.sharding import PartitionSpec as P

            from ..launch.mesh import make_mesh
            mesh = make_mesh((n_dev,), ("fleet",))
            # consts replicated, lane axis split; each shard drains its
            # lanes independently (no collectives — the chunk's early exit
            # is a shard-local jnp.all over its own done flags)
            fn = jax.shard_map(counted, mesh=mesh,
                               in_specs=(P(), P("fleet"), P("fleet")),
                               out_specs=(P("fleet"), P("fleet")),
                               check_vma=False)
        # donating the carry lets XLA alias it through the while loop
        # (jaxcheck:donation); the caller never reads a carry it passed in
        return jax.jit(fn, donate_argnums=runners.DONATE_ARGNUMS)

    return runners.get_cached_program(key, build)


def _refill_program(meta: SimMeta, width: int) -> Callable:
    """Cached jitted refill: ``(mask, consts, carry) -> carry`` with
    refilled lanes reset to the t=0 carry.  The t=0 carry is rebuilt from
    ``consts`` inside the program rather than kept from cohort start: the
    chunk donates every carry it is given, so a kept copy would share
    buffers the first chunk call deletes.  Eager ``tree_select`` is ~70
    per-leaf dispatches per chunk boundary — a large fraction of host time
    on fast tiers."""
    key = ("fleet-refill", meta, width)
    return runners.get_cached_program(
        key, lambda: jax.jit(lambda mask, c, carry: tree_select(
            mask, init_fleet_carry(c, meta, width), carry)))


def _init_program(meta: SimMeta, width: int) -> Callable:
    """Cached jitted cohort initializer: ``(consts, pad) -> t=0 carry``
    with the ``pad`` lanes (``[W]`` bool) marked done.  Eager
    ``init_fleet_carry`` dispatches ~35 broadcast ops plus the endpoint
    cache per cohort (~6 ms on the small tier — comparable to a whole
    chunk); jitted it is one cached executable per (meta, width)."""
    key = ("fleet-init", meta, width)

    def init(c, pad):
        s0, cache0, done0 = init_fleet_carry(c, meta, width)
        return s0, cache0, done0 | pad

    return runners.get_cached_program(key, lambda: jax.jit(init))


def _lane_policies(pol_np: Dict[str, np.ndarray],
                   sched: CohortSchedule) -> Dict[str, np.ndarray]:
    """[W]-shaped lane-varying policy rows (static fields excluded)."""
    out = {}
    for k, col in pol_np.items():
        if k in STATIC_FIELDS:
            continue
        rows = [col[m] if m is not None else col[0] for m in sched.lane]
        out[k] = np.stack(rows)
    return out


def run_fleet(exp, width: int = 32, chunk_steps: int = 32,
              devices: Optional[int] = None, return_stats: bool = False,
              predictor: Optional[StepPredictor] = None):
    """Drain an ``Experiment``'s scenario × policy grid through the fleet
    engine (DESIGN.md §9) and assemble the same ``Results`` grid
    ``Experiment.run`` returns, bit-identically.

    Parameters: ``width`` lanes per cohort (rounded up to a multiple of the
    device count); ``chunk_steps`` events per jitted chunk (K); ``devices``
    caps the fleet mesh (default: all visible devices); ``return_stats``
    additionally returns a ``FleetStats``.
    """
    predictor = predictor or _PREDICTOR
    S, P = len(exp.scenarios), len(exp.policies)
    consts, meta = exp.build()
    meta = SimMeta.coerce(meta)

    n_dev = devices if devices is not None else jax.local_device_count()
    n_dev = max(1, min(n_dev, jax.local_device_count()))

    stats = FleetStats(sims=S * P, devices=n_dev)
    policy_arrays = exp.policy_arrays()
    with TraceAnnotation("repro.fleet.cohort"):
        pol_np = {k: stats.fetch(v) for k, v in policy_arrays.items()}

        # group the policy axis by static signature: one cohort per
        # (scenario, sig) shares one specialized chunk program
        groups: Dict[Tuple[int, ...], List[int]] = {}
        for p in range(P):
            sig = tuple(int(pol_np[f][p]) for f in STATIC_FIELDS)
            groups.setdefault(sig, []).append(p)

    # final [S, P, ...] state grid, allocated once and written in place at
    # retire time (one vectorized row-gather per leaf per boundary — per-sim
    # tree copies cost ~leaves × sims tiny np ops and dominated the host
    # side of small-tier fleets)
    out: Optional[List[np.ndarray]] = None
    state_cls = None

    for si in range(S):
        with TraceAnnotation("repro.fleet.cohort"):
            if S == 1:
                consts_s = consts
            else:
                from ..scenarios.sweep import slice_packed
                consts_s = slice_packed(consts, si)
            n_tasks = int(np.sum(stats.fetch(consts_s.task_valid)))
            n_pkts = int(np.sum(stats.fetch(consts_s.pkt_valid)))
        sname = exp.scenario_names[si]

        for sig, members in groups.items():
            with TraceAnnotation("repro.fleet.cohort"):
                gkey = (sname, sig)
                order = sorted(members, key=lambda p: predictor.predict(
                    (sname, sig, exp.policy_names[p]), gkey, n_tasks,
                    n_pkts))
                W = min(width, len(order))
                if n_dev > 1:
                    W = n_dev * math.ceil(W / n_dev)
                sched = CohortSchedule(order, W)
                stats.cohorts += 1
                stats.width = max(stats.width, W)

                chunk = _chunk_program(meta, sig, chunk_steps, W, n_dev)
                carry = _init_program(meta, W)(
                    consts_s, stats.upload(sched.pad_mask()))

                # hard backstop: every member can run at most max_steps
                # events
                max_chunks = ((len(order) + W)
                              * (meta.max_steps // chunk_steps + 2))
                chunks = 0
                pol_lane = _lane_policies(pol_np, sched)
                # the chunks' step counts, summed on the device and
                # fetched once per cohort: no extra sync per chunk
                counts = None
            while sched.active:
                with TraceAnnotation("repro.fleet.chunk"):
                    stats.count_uploads(pol_lane)
                    carry, n = chunk(consts_s, pol_lane, carry)
                    counts = n if counts is None else counts + n
                chunks += 1
                stats.chunks += 1
                if chunks > max_chunks:
                    raise RuntimeError(
                        f"fleet cohort {gkey} exceeded {max_chunks} chunks "
                        "without draining — engine not making progress")
                with TraceAnnotation("repro.fleet.sync"):
                    done = stats.fetch(carry[2])
                with TraceAnnotation("repro.fleet.retire"):
                    retire, refill = sched.step(done)
                    if retire:
                        host_s = [stats.fetch(a) for a in carry[0]]
                        if out is None:
                            state_cls = type(carry[0])
                            out = [np.empty((S, P) + a.shape[1:], a.dtype)
                                   for a in host_s]
                        lanes = np.array([l for l, _ in retire])
                        mems = np.array([m for _, m in retire])
                        for o, h in zip(out, host_s):
                            o[si, mems] = h[lanes]
                        steps_leaf = host_s[carry[0]._fields.index("steps")]
                        for lane, member in retire:
                            steps = float(steps_leaf[lane])
                            predictor.observe(
                                (sname, sig, exp.policy_names[member]),
                                steps)
                            predictor.observe(gkey, steps)
                if refill.any():
                    with TraceAnnotation("repro.fleet.refill"):
                        stats.refills += int(refill.sum())
                        mask = stats.upload(refill)
                        # where refilled: back to the t=0 carry (done leaf
                        # included — a sim finished at t=0 stays frozen
                        # and retires with its s0 state, exactly like
                        # serial)
                        carry = _refill_program(meta, W)(mask, consts_s,
                                                         carry)
                        pol_lane = _lane_policies(pol_np, sched)
            steps, fails = stats.fetch(counts).sum(0)
            stats.chunk_steps += int(steps)
            stats.fail_steps += int(fails)

    states = state_cls(*out)   # the serial runner's [S, P, ...] grid
    if S == 1:   # Results keeps a scenario axis on consts
        consts = jax.tree_util.tree_map(lambda a: a[None], consts)
    res = Results(states=states, consts=consts, meta=meta,
                  scenario_names=exp.scenario_names,
                  policy_names=exp.policy_names)
    return (res, stats) if return_stats else res
