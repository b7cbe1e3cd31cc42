"""Batched policy-sweep engine: N policies × M traces in ONE ``lax.scan``.

Every benchmark in the reproduction compares page-table placement policies
on identical access traces.  Running them as separate Python-loop
iterations compiles one scan per policy and pays a device round-trip each;
this module instead stacks the policies (and optionally several same-shape
padded traces) into a leading *lane* axis, vmaps the policy-generic
simulator step (``sim._build_step``) over it, and runs the whole grid as a
single compiled ``lax.scan`` — one compile per trace shape, one device
program per figure.

:func:`sweep_lanes` is the one engine entry point — one lane per
independent ``(cost, policy, trace)`` tuple, the microbatch primitive of
the simulation service (``repro.service``): a broker bucketing arbitrary
concurrent queries by trace shape flushes each bucket through one call
here.  It does the host prepare, holds the one compile cache, dispatches,
reads back, and counts and times.  Two facades call it:

  * :func:`sweep` — the figure-style cross product: N policies × M traces;
  * :class:`TieredMemSimulator` — one trace under one policy bundle, run
    as a one-lane sweep.

Execution is time-blocked by default (``engine="blocked"``, see
``core.sim``): the scan iterates fixed ``[block, T]`` step-windows,
host-classified from the *union* event schedule over lanes (frees,
AutoNUMA ticks, faults — union predicates, like the per-step schedule
bits before them, so block boundaries stay lane-shared and
policy-independent).  Event-free windows run as one vectorized
fast-path step per lane; a window whose only event is a single scan
tick hoists it between two fast segments; narrow event spans replay
per-step only inside the span; wide spans replay the whole window, through
a cond-free row body where no row frees, ticks or is fault-free.
Window count depends only on the trace *shape* and the segment
capacities are pow2-quantized into the compile key
(``sim.plan_windows``), so the compiled-program quantization the
broker's shape buckets rely on is untouched.  ``engine="per_step"``
keeps the step-at-a-time reference scan.

Lanes can additionally be sharded across devices (``lane_sharding`` —
``jax.sharding`` over the lane axis): the state pytree and every per-lane
input are placed with a ``PartitionSpec`` over a 1-D ``"lanes"`` mesh, so
a policy grid spreads over all local devices with no change to the scan
body.  On a single-device host the mesh degenerates and results are
bit-identical to the unsharded path.

Correctness contract: a sweep lane is bit-identical (placements,
counters, cycles and timeline — and bit-exact between the blocked and
per-step engines) to the corresponding one-lane ``TieredMemSimulator``
run, and matches the pure-Python ``core.ref`` oracle (cycles to float32
rounding) — ``tests/test_sweep.py``, ``tests/test_blocked.py`` and
``tests/test_service.py`` enforce these.

Constraints inherited from the step being compiled once for all lanes:

  * all traces must share one ``[steps, threads]`` shape (``pad_trace``);
  * all AutoNUMA-enabled policies must share ``autonuma_period`` (the scan
    schedule is a host-precomputed, lane-shared predicate so ``lax.cond``
    survives vmap);
  * the AutoNUMA ``top_k`` bound is the max ``autonuma_budget`` over the
    swept policies (or the explicit ``budget`` override, which may only
    raise it); per-lane budgets gate through traced masks, so an
    over-provisioned bound never changes results — brokers quantize it to
    keep compile keys stable across bursts;
  * lanes resumed from ``states`` must share one step clock (the scan
    schedule again).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs import or_null
from .config import CostConfig, MachineConfig, PolicyConfig
from .sim import (DEFAULT_BLOCK, RunResult, SCHED_DO, TIMELINE_KEYS, Trace,
                  _build_blocked_body, _build_step, _normalize_blocked,
                  fault_schedule, plan_windows, pow2ceil, scan_rows,
                  scan_step_mask, seg_of_leaf_table, window_tiles)
from .state import SimState, init_state

I32 = jnp.int32
F32 = jnp.float32

# One jitted vmapped scan per (machine, budget, engines, block, split
# geometry); jax's jit cache then holds one executable per (lane count,
# trace shape, lane sharding).
_SWEEP_CACHE: Dict[Tuple, object] = {}


def compile_count() -> int:
    """Number of XLA compilations performed by sweep()/sweep_lanes() so far.

    Counts entries in the underlying jit caches (one per distinct
    (machine, budget, engine, lane-count, trace-shape, sharding)
    combination) — tests assert a ≥4-policy sweep adds exactly one and
    that a service-cache hit adds zero.
    """
    return int(sum(fn._cache_size() for fn in _SWEEP_CACHE.values()))


def stack_policies(policies: Sequence[PolicyConfig]) -> PolicyConfig:
    """Stack N PolicyConfigs into one whose leaves are ``[N]`` arrays."""
    return _stack_leaves(list(policies))


def _stack_leaves(objs):
    def stack(*leaves):
        a = np.stack([np.asarray(leaf) for leaf in leaves])
        if a.dtype.kind in "iu":
            return jnp.asarray(a, I32)
        if a.dtype.kind == "f":
            return jnp.asarray(a, F32)
        return jnp.asarray(a)
    return jax.tree.map(stack, *objs)


def _sweep_runner(mc: MachineConfig, budget: int, phase_b: str,
                  engine: str, block: int, geom=None):
    if engine == "blocked":
        budget, phase_b = _normalize_blocked(budget, phase_b, geom)
    key = (mc, budget, phase_b, engine, block, geom)
    if key not in _SWEEP_CACHE:
        if engine == "per_step":
            # the schedule predicates stay un-batched so the step's
            # lax.conds keep skipping work; the step vmaps its per-lane
            # parts itself
            run_rows = _build_step(mc, budget, phase_b)
        else:
            # the window body: kind dispatch, fast/full/hoist/split
            # branches, lane vmaps
            run_rows = _build_blocked_body(mc, budget, phase_b, block, geom)
        _SWEEP_CACHE[key] = jax.jit(scan_rows(run_rows))
    return _SWEEP_CACHE[key]


def check_engine(engine: str, phase_b: str, debug: bool) -> None:
    """The reference paths (``engine="per_step"``, ``phase_b=
    "sequential"``) are differential-testing oracles: they run only with
    ``debug=True``."""
    if engine not in ("blocked", "per_step"):
        raise ValueError(f"unknown engine {engine!r}")
    if (engine != "blocked" or phase_b != "batched") and not debug:
        raise ValueError(
            f"engine={engine!r} phase_b={phase_b!r} are reference (oracle) "
            "paths; pass debug=True to run them")


def event_masks(mc: MachineConfig, traces: Sequence[Trace], period: int,
                autonuma: bool, start_step: int = 0):
    """The host schedule of a call: each trace's fault schedule and the
    lane-union step predicates ``(do_free, do_scan, has_fault)`` that
    :func:`sim.plan_windows` classifies windows from.  ``start_step`` is
    the step clock the lanes resume from (the scan ticks' phase)."""
    S = traces[0].va.shape[0]
    scheds = [fault_schedule(tr, mc) for tr in traces]
    do_free = np.zeros((S,), bool)
    has_fault = np.zeros((S,), bool)
    for sc, tr in zip(scheds, traces):
        do_free |= np.asarray(tr.free_seg) >= 0
        has_fault |= (sc & SCHED_DO).any(axis=1)
    do_scan = scan_step_mask(S, period, enabled=autonuma,
                             start_step=start_step)
    return scheds, do_free, do_scan, has_fault


def lane_mesh(n_lanes: int, devices=None) -> Mesh:
    """A 1-D ``"lanes"`` mesh over the largest device prefix dividing
    ``n_lanes`` (every device on an evenly divisible lane count; one
    device — the degenerate mesh — when nothing divides)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    while n > 1 and n_lanes % n:
        n -= 1
    return Mesh(np.asarray(devices[:n]), ("lanes",))


def _resolve_lane_sharding(lane_sharding, n_lanes: int) -> Optional[Mesh]:
    if lane_sharding is None:
        return None
    if lane_sharding == "auto":
        return lane_mesh(n_lanes)
    if isinstance(lane_sharding, Mesh):
        if n_lanes % lane_sharding.devices.size:
            raise ValueError(
                f"{n_lanes} lanes not divisible by the {lane_sharding.devices.size}-"
                "device lane mesh")
        return lane_sharding
    raise ValueError(f"lane_sharding must be None, 'auto' or a Mesh, got "
                     f"{lane_sharding!r}")


def sweep_lanes(mc: MachineConfig,
                ccs: Sequence[CostConfig],
                policies: Sequence[PolicyConfig],
                traces: Sequence[Trace],
                phase_b: str = "batched",
                budget: Optional[int] = None,
                lane_sharding=None,
                engine: str = "blocked",
                block: int = DEFAULT_BLOCK,
                debug: bool = False,
                telemetry=None,
                states: Optional[Sequence[SimState]] = None,
                ) -> List[RunResult]:
    """Run L independent (cost, policy, trace) lanes as one batched scan.

    The service-broker primitive: unlike :func:`sweep` there is no cross
    product — lane ``i`` simulates ``traces[i]`` under ``policies[i]`` /
    ``ccs[i]``.  All traces must share one ``[steps, threads]`` shape
    (shape-bucketing is the caller's job; see ``repro.service.broker``).

    ``budget`` (optional) raises the compiled AutoNUMA ``top_k`` bound
    above the per-lane maximum so repeated calls with different policy
    mixes reuse one executable; per-lane budgets still gate exactly.

    ``engine`` / ``block`` select the stepper (see ``core.sim``):
    time-blocked windows by default, with event windows — the union over
    lanes, so block boundaries stay lane-shared and policy-independent —
    falling back to the exact per-step path.

    ``lane_sharding`` — ``None`` (single device), ``"auto"`` (shard the
    lane axis over every local device that divides the lane count), or an
    explicit 1-D ``"lanes"`` :class:`jax.sharding.Mesh`.

    The per-step engine and the sequential fault path are reference
    (oracle) configurations kept for differential testing; production
    callers get the blocked/batched fast path.  Pass ``debug=True`` to
    run a reference path deliberately.

    ``states`` (optional, one :class:`SimState` per lane) resumes each
    lane from its own state instead of a fresh ``init_state``; the lanes
    must share one step clock (``SimState.step``), since the scan
    schedule is lane-shared.

    ``telemetry`` (optional :class:`repro.obs.Telemetry`) times the
    call's layer boundaries as spans: ``sweep.prepare`` (entry to
    dispatch) holding ``sweep.schedule`` (:func:`event_masks`: fault
    schedules and event masks), ``sweep.plan`` (lane stacking, window
    plan and tiles) and ``sweep.stage`` (host-to-device copies, initial state,
    shardings, runner lookup); ``sweep.device`` (dispatch to
    ``block_until_ready``, compile included on a cold call) and
    ``sweep.readback``.  It counts lanes, windows by kind (the lean ones
    among the full, ``sweep.windows_lean``), the rows the window scan
    covers (``sweep.rows``), the rows the per-step body replays
    (``sweep.replay_rows``) and the scan ticks by whether they run
    hoisted or replayed (``sweep.scan_ticks{arm=hoist|replay}``), and
    after the run the replayed faulting rows by the allocator arm they
    took (``sweep.alloc_rows{path=batched|scan}``).  On the
    device the window kinds, step phases, allocator arms and hoisted scan
    ticks carry ``jax.named_scope`` names (``window.*``, ``step.*``,
    ``fault.alloc_batched|scan``, ``mig.scan``; see
    ``sim._build_blocked_body``).  Every hook is
    host-side Python: the compiled program and its outputs are
    bitwise-identical with telemetry on or off.
    """
    tel = or_null(telemetry)
    check_engine(engine, phase_b, debug)
    policies = list(policies)
    ccs = list(ccs)
    tr_list = list(traces)
    L = len(policies)
    if L == 0:
        raise ValueError("sweep_lanes needs at least one lane")
    if not (len(ccs) == len(tr_list) == L):
        raise ValueError(
            f"lane lists disagree: {len(ccs)} costs, {L} policies, "
            f"{len(tr_list)} traces")
    start = 0
    if states is not None:
        states = list(states)
        if len(states) != L:
            raise ValueError(f"{len(states)} states for {L} lanes")
        clocks = sorted({int(np.asarray(st.step)) for st in states})
        if len(clocks) > 1:
            raise ValueError(
                f"resumed lanes must share one step clock, got {clocks}; "
                "the scan schedule is lane-shared")
        start = clocks[0]
    shape = tr_list[0].va.shape
    S = shape[0]

    with tel.span("sweep.prepare", lanes=L, steps=S, engine=engine):
        for tr in tr_list:
            if tr.va.shape != shape:
                raise ValueError(
                    f"sweep traces must share one shape; got {tr.va.shape} "
                    f"vs {shape} — pad_trace() them first")
        if shape[1] != mc.n_threads:
            raise ValueError(f"traces have {shape[1]} threads, machine has "
                             f"{mc.n_threads}")

        periods = sorted({int(p.autonuma_period) for p in policies
                          if bool(p.autonuma)})
        if len(periods) > 1:
            raise ValueError(
                f"swept policies must share autonuma_period, got {periods}; "
                "the scan schedule is lane-shared")
        period = periods[0] if periods else int(policies[0].autonuma_period)
        lane_budget = min(max(int(p.autonuma_budget) for p in policies),
                          mc.n_map)
        if budget is not None and budget < lane_budget:
            raise ValueError(f"budget override {budget} below the lane "
                             f"maximum {lane_budget}; a smaller top_k bound "
                             "changes results")
        eff_budget = min(budget if budget is not None else lane_budget,
                         mc.n_map)

        # Host arrays are built per *unique trace object* and fanned out to
        # lanes by index, so a bucket of queries sharing one trace pays one
        # schedule pass and one stack.
        uniq: Dict[int, int] = {}
        uniq_traces: List[Trace] = []
        lane_of = np.empty((L,), np.int64)
        for i, tr in enumerate(tr_list):
            j = uniq.setdefault(id(tr), len(uniq_traces))
            if j == len(uniq_traces):
                uniq_traces.append(tr)
            lane_of[i] = j

        with tel.span("sweep.schedule"):
            scheds, do_free, do_scan, has_fault = event_masks(
                mc, uniq_traces, period,
                any(bool(p.autonuma) for p in policies), start)

        def lanes(per_trace, dtype):
            a = np.stack([np.asarray(x, dtype) for x in per_trace], axis=1)
            return a[:, lane_of]

        with tel.span("sweep.plan"):
            va = lanes([tr.va for tr in uniq_traces], np.int32)   # [S, L, T]
            wr = lanes([tr.is_write for tr in uniq_traces], bool)
            fid = lanes([tr.free_seg for tr in uniq_traces], np.int32)
            llc = lanes([tr.llc for tr in uniq_traces], np.float32)
            sched = lanes(scheds, np.uint8)                       # [S, L, T]
            eff_block = min(int(block), pow2ceil(S))
            plan = None
            if engine == "per_step":
                host_xs = (va, wr, fid, llc, sched, do_free, do_scan,
                           has_fault, np.ones((S,), bool))
                lane_axis_of_x = (1, 1, 1, 1, 1, None, None, None, None)
            else:
                # window classification from the lane-union schedule; pad
                # rows take sim.WINDOW_PAD_FILLS
                plan = plan_windows(do_free, do_scan, has_fault, S,
                                    eff_block)
                host_xs = tuple(window_tiles(
                    (va, wr, fid, llc, sched, np.ones((S,), bool), do_free,
                     do_scan, has_fault),
                    S, eff_block, rows_to=plan.rows_in)) + (
                        plan.kind, plan.seg_a, plan.seg_b)
                # windowed lane arrays carry the lane axis at position 2
                lane_axis_of_x = (2, 2, 2, 2, 2, None, None, None, None,
                                  None, None, None)

        with tel.span("sweep.stage"):
            xs = tuple(jnp.asarray(a) for a in host_xs)
            lane_pc = _stack_leaves(policies)
            lane_cc = _stack_leaves(ccs)
            seg_maps = np.stack([np.asarray(tr.seg_of_map, np.int32)
                                 for tr in uniq_traces])
            seg_of_map = jnp.asarray(seg_maps[lane_of])          # [L, n_map]
            seg_leafs = np.stack([np.asarray(seg_of_leaf_table(tr, mc))
                                  for tr in uniq_traces])
            seg_of_leaf = jnp.asarray(seg_leafs[lane_of])        # [L, n_leaf]
            if states is None:
                st0 = jax.tree.map(
                    lambda a: jnp.broadcast_to(a[None], (L,) + a.shape),
                    init_state(mc))
            else:
                st0 = _stack_leaves(states)

            mesh = _resolve_lane_sharding(lane_sharding, L)
            if mesh is not None:
                lane_sh = NamedSharding(mesh, P("lanes"))
                rep_sh = NamedSharding(mesh, P())
                put = jax.device_put
                st0 = jax.tree.map(lambda a: put(a, lane_sh), st0)
                lane_cc = jax.tree.map(lambda a: put(a, lane_sh), lane_cc)
                lane_pc = jax.tree.map(lambda a: put(a, lane_sh), lane_pc)
                xs = tuple(
                    put(x, rep_sh if ax is None else NamedSharding(
                        mesh, P(*([None] * ax + ["lanes"]))))
                    for x, ax in zip(xs, lane_axis_of_x))
                seg_of_map = put(seg_of_map, lane_sh)
                seg_of_leaf = put(seg_of_leaf, lane_sh)

            geom = plan.geom if plan is not None else None
            run_sweep = _sweep_runner(mc, eff_budget, phase_b, engine,
                                      eff_block, geom)

        if tel.enabled:
            tel.counter("sweep.calls", engine=engine).inc()
            tel.counter("sweep.lanes", engine=engine).inc(L)
            if engine == "blocked":
                n_fast, _, n_hoist, n_split = plan.counts
                tel.counter("sweep.windows_event").inc(
                    plan.n_windows - n_fast)
                tel.counter("sweep.windows_fast").inc(n_fast)
                tel.counter("sweep.windows_hoist").inc(n_hoist)
                tel.counter("sweep.windows_split").inc(n_split)
                tel.counter("sweep.windows_lean").inc(plan.n_lean)
                tel.counter("sweep.rows").inc(plan.n_windows * eff_block)
                tel.counter("sweep.replay_rows").inc(plan.replay_rows)
                hoisted, replayed = plan.scan_ticks
                tel.counter("sweep.scan_ticks", arm="hoist").inc(hoisted)
                tel.counter("sweep.scan_ticks", arm="replay").inc(replayed)
            else:
                tel.counter("sweep.steps").inc(S)
                tel.counter("sweep.rows").inc(S)
                tel.counter("sweep.replay_rows").inc(S)

    with tel.span("sweep.device", lanes=L, steps=S, engine=engine):
        final, outs, alloc_rows = jax.block_until_ready(
            run_sweep(st0, lane_cc, lane_pc, xs, seg_of_map, seg_of_leaf))
    with tel.span("sweep.readback"):
        final = jax.device_get(final)
        outs = [np.asarray(o) for o in jax.device_get(outs)]
        if tel.enabled:
            # replayed faulting rows by the allocator arm they took: the
            # one-pass vector arm or the serialized slot scan
            for path, n in zip(("batched", "scan"), np.asarray(alloc_rows)):
                tel.counter("sweep.alloc_rows", path=path).inc(int(n))
    if engine == "blocked":
        # [n_windows, R_out, L] -> [steps, L]: pad and capacity-slack
        # rows dropped in step order via the plan's emission mask
        outs = [o[plan.emit_valid] for o in outs]

    results: List[RunResult] = []
    for i, (pc, tr) in enumerate(zip(policies, tr_list)):
        st_lane = jax.tree.map(lambda a: a[i], final)
        timeline = {k: v[:, i] for k, v in zip(TIMELINE_KEYS, outs)}
        results.append(RunResult(final_state=st_lane, timeline=timeline,
                                 trace_name=tr.name,
                                 policy_label=pc.label()))
    return results


def sweep(mc: MachineConfig,
          cc: Union[CostConfig, Sequence[CostConfig]],
          policies: Sequence[PolicyConfig],
          traces: Union[Trace, Sequence[Trace]],
          phase_b: str = "batched",
          budget: Optional[int] = None,
          lane_sharding=None,
          engine: str = "blocked",
          block: int = DEFAULT_BLOCK,
          debug: bool = False,
          telemetry=None,
          ) -> Union[List[RunResult], List[List[RunResult]]]:
    """Run every (trace, policy) pair as one batched compiled scan.

    Returns a list of RunResults aligned with ``policies`` when ``traces``
    is a single Trace, else a list-of-lists indexed ``[trace][policy]``.
    ``cc`` may be a single CostConfig (shared) or one per policy.
    ``phase_b`` selects the fault engine and ``engine``/``block`` the
    stepper (see ``TieredMemSimulator``); the default batched fault
    engine removed the per-thread ``lax.cond`` vmap penalty, the default
    blocked stepper batches event-free step windows.  ``budget`` and
    ``lane_sharding`` pass through to :func:`sweep_lanes`.
    """
    single = isinstance(traces, Trace)
    tr_list = [traces] if single else list(traces)
    policies = list(policies)
    P_, M = len(policies), len(tr_list)
    if P_ == 0 or M == 0:
        raise ValueError("sweep needs at least one policy and one trace")

    ccs = list(cc) if isinstance(cc, (list, tuple)) else [cc] * P_
    if len(ccs) != P_:
        raise ValueError("need one CostConfig per policy (or a shared one)")

    # Lane layout: trace-major, policy-minor (lane = trace_idx * P + pol_idx).
    flat = sweep_lanes(
        mc,
        [c for _ in range(M) for c in ccs],
        [p for _ in range(M) for p in policies],
        [tr for tr in tr_list for _ in range(P_)],
        phase_b=phase_b, budget=budget, lane_sharding=lane_sharding,
        engine=engine, block=block, debug=debug, telemetry=telemetry)
    results = [flat[j * P_:(j + 1) * P_] for j in range(M)]
    return results[0] if single else results


class TieredMemSimulator:
    """Public facade: configure once, run traces under a policy bundle.

    A run is a one-lane :func:`sweep_lanes` call, so it shares the sweep's
    compile cache and its results are those of the matching sweep lane,
    bit for bit.

    ``phase_b`` selects the fault engine: ``"batched"`` (default, the
    conflict-aware vectorized path) or ``"sequential"`` (the per-thread
    ``fori_loop`` reference the batched engine is tested against).

    ``engine`` selects the stepper: ``"blocked"`` (default — the
    time-blocked fast path over ``block``-step windows, bit-identical to
    per-step execution) or ``"per_step"`` (the retained one-step-per-scan
    reference).  The reference paths are differential-testing oracles
    and run only with ``debug=True`` (:func:`check_engine`).

    ``telemetry`` (optional :class:`repro.obs.Telemetry`) records a
    ``sim.run`` span holding the sweep's own spans and counters
    (``sweep.*``, see :func:`sweep_lanes`), and ``sim.runs{engine=}``.
    All hooks are host-side: the compiled program and its outputs are
    bitwise-identical with telemetry on or off.
    """

    def __init__(self, mc: MachineConfig = MachineConfig(),
                 cc: CostConfig = CostConfig(),
                 pc: PolicyConfig = PolicyConfig(),
                 phase_b: str = "batched",
                 engine: str = "blocked",
                 block: int = DEFAULT_BLOCK,
                 debug: bool = False,
                 telemetry=None):
        check_engine(engine, phase_b, debug)
        self.mc, self.cc, self.pc = mc, cc, pc
        self.phase_b = phase_b
        self.engine = engine
        self.block = int(block)
        self.debug = bool(debug)
        self.telemetry = or_null(telemetry)

    def run(self, trace: Trace, state: Optional[SimState] = None) -> RunResult:
        """Simulate ``trace`` from ``state`` (a fresh machine by default;
        a previous run's ``final_state`` continues it)."""
        tel = self.telemetry
        with tel.span("sim.run", steps=trace.n_steps, engine=self.engine,
                      trace=trace.name):
            res, = sweep_lanes(
                self.mc, [self.cc], [self.pc], [trace],
                phase_b=self.phase_b, engine=self.engine, block=self.block,
                debug=self.debug, telemetry=tel,
                states=None if state is None else [state])
        tel.counter("sim.runs", engine=self.engine).inc()
        return res
