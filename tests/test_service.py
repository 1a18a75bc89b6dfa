"""Event-driven scheduling service: event-loop ordering, replay determinism,
the solve-cache hot path (zero solver invocations on repeats), admission
batching, node drift/failure handling, trace I/O, and the serve CLI."""

import json
import math
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import Task, Workflow, make_system, Node
from repro.core.workload_model import mri_w1
from repro.service import (
    EventLoop,
    SchedulingService,
    ServiceConfig,
    Submission,
    Trace,
    continuum_system,
    generate_trace,
    load_trace,
    trace_from_json,
)
from repro.service.traces import NodeEvent


# ---------------------------------------------------------------------------
# event loop
# ---------------------------------------------------------------------------

def test_event_loop_orders_by_time_then_push_order():
    loop = EventLoop()
    loop.push(5.0, "b")
    loop.push(1.0, "a")
    loop.push(5.0, "c")  # same time as "b": push order breaks the tie
    kinds = [ev.kind for ev in loop.drain()]
    assert kinds == ["a", "b", "c"]
    assert loop.now == 5.0


def test_event_loop_clamps_past_pushes_to_now():
    loop = EventLoop()
    loop.push(10.0, "later")
    assert loop.pop().kind == "later"
    ev = loop.push(3.0, "too-early")  # in the past: clamps to now
    assert ev.time == 10.0
    assert loop.pop().time == 10.0


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _single_node_system(speed: float = 1.0):
    return make_system([
        Node("N1", {"cores": 8}, frozenset({"F1"}),
             {"processing_speed": speed, "data_transfer_rate": 100.0}),
    ])


def _two_node_system():
    return make_system([
        Node("N1", {"cores": 8}, frozenset({"F1"}),
             {"processing_speed": 1.0, "data_transfer_rate": 100.0}),
        Node("N2", {"cores": 8}, frozenset({"F1"}),
             {"processing_speed": 4.0, "data_transfer_rate": 100.0}),
    ])


def _chain(name: str, works) -> Workflow:
    tasks = [
        Task(
            f"T{i}",
            cores=2,
            work=float(w),
            features=frozenset({"F1"}),
            deps=(f"T{i - 1}",) if i else (),
        )
        for i, w in enumerate(works)
    ]
    return Workflow(name, tuple(tasks))


def _sub(i, wf, t, technique="heft", **kw) -> Submission:
    return Submission(
        id=f"s{i:03d}", tenant="t0", time=float(t), family="test",
        workflow=wf, technique=technique, **kw,
    )


# ---------------------------------------------------------------------------
# acceptance: replay determinism
# ---------------------------------------------------------------------------

def test_replay_same_trace_and_seed_is_bit_identical():
    """Same trace + seed ⇒ identical event log and per-submission makespans."""
    trace = generate_trace(
        14, seed=11, rate=3.0, families=("mri", "tpu"), node_events=True,
    )
    results = []
    for _ in range(2):
        svc = SchedulingService(trace.system, ServiceConfig(seed=11))
        results.append(svc.run(trace))
    a, b = results
    assert a.event_log == b.event_log
    assert a.makespans() == b.makespans()
    assert [r.to_json() for r in a.records] == [r.to_json() for r in b.records]


def test_replay_determinism_with_jitter():
    """Jitter draws from per-submission derived seeds — still replayable."""
    trace = generate_trace(6, seed=2, families=("tpu",))
    cfg = ServiceConfig(seed=5, jitter=0.1)
    a = SchedulingService(trace.system, cfg).run(trace)
    b = SchedulingService(trace.system, cfg).run(trace)
    assert a.event_log == b.event_log
    assert a.makespans() == b.makespans()


# ---------------------------------------------------------------------------
# acceptance: the cache hot path
# ---------------------------------------------------------------------------

def test_repeat_identical_submission_zero_solver_invocations():
    subs = tuple(_sub(i, mri_w1(), t=i * 30.0) for i in range(4))
    trace = Trace(name="rep", system=continuum_system(), submissions=subs)
    svc = SchedulingService(trace.system, ServiceConfig())
    r = svc.run(trace)
    assert [rec.status for rec in r.records] == ["completed"] * 4
    assert r.solver_calls == 1  # only the first submission reached a solver
    assert [rec.cache_hit for rec in r.records] == [False, True, True, True]
    assert r.cache["hits"] == 3 and r.cache["misses"] == 1
    # all four executed identically (same model, no perturbation)
    mk = [rec.observed_makespan for rec in r.records]
    assert mk[0] == pytest.approx(mk[1]) == pytest.approx(mk[3])


def test_burst_of_identical_submissions_coalesces_in_one_window():
    """Duplicates arriving inside one admission window solve once: the first
    solves, its twins pick the result up at admission."""
    subs = tuple(_sub(i, mri_w1(), t=0.0) for i in range(5))
    trace = Trace(name="burst", system=continuum_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig(batch_window=1.0)).run(trace)
    assert r.solver_calls == 1
    assert sum(rec.cache_hit for rec in r.records) == 4
    # the summary metric agrees with the per-record flags: 4 submissions
    # skipped the solver (coalesced twins count as hits, not misses)
    assert r.cache["hits"] == 4 and r.cache["misses"] == 1


# ---------------------------------------------------------------------------
# admission batching
# ---------------------------------------------------------------------------

def test_admission_batches_same_bucket_ga_submissions():
    """Distinct-content, same-shape GA submissions in one window route
    through the registry batch path as ONE group."""
    opts = {"generations": 3, "pop_size": 8, "seed": 0}
    subs = tuple(
        _sub(i, _chain(f"C{i}", [1.0 + i, 2.0, 3.0 + i, 1.0, 2.0, 1.0]),
             t=0.0, technique="ga", solver_options=opts)
        for i in range(3)
    )
    trace = Trace(name="batch", system=_two_node_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig(batch_window=1.0)).run(trace)
    assert r.batched_groups == 1
    assert r.batched_submissions == 3
    assert all(rec.batched for rec in r.records)
    assert all(rec.status == "completed" for rec in r.records)
    assert r.solver_calls == 3  # three problems solved, one compiled program


def test_bad_options_in_batch_group_reject_without_killing_the_service():
    """A solver error inside a *batched* group must degrade exactly like the
    single-solve path: the group falls back to per-submission solves and only
    the culprits are rejected — the service run itself survives."""
    bad = {"generations": 2, "pop_size": 0, "seed": 0}  # zero-size population
    subs = (
        _sub(0, _chain("A", [1.0, 2.0]), t=0.0, technique="ga", solver_options=bad),
        _sub(1, _chain("B", [2.0, 3.0]), t=0.0, technique="ga", solver_options=bad),
        _sub(2, _chain("C", [1.0, 1.0]), t=0.0, technique="heft"),
    )
    trace = Trace(name="badbatch", system=_two_node_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig(batch_window=1.0)).run(trace)
    assert [rec.status for rec in r.records] == ["rejected", "rejected", "completed"]


def test_record_json_is_strict_even_for_rejected_submissions():
    """Rejected records keep NaN timestamps internally but must serialize to
    strict JSON (null, not bare NaN tokens)."""
    wf = Workflow("needs-f2", (Task("T0", features=frozenset({"F2"})),))
    trace = Trace(name="nan", system=_single_node_system(),
                  submissions=(_sub(0, wf, t=0.0),))
    r = SchedulingService(trace.system, ServiceConfig()).run(trace)
    obj = r.records[0].to_json()
    assert obj["status"] == "rejected"
    assert obj["finished"] is None and obj["observed_makespan"] is None
    json.dumps([rec.to_json() for rec in r.records], allow_nan=False)  # no raise


def test_typoed_solver_option_rejects_one_tenant_not_the_service():
    """Misspelled solver_options raise TypeError inside the technique —
    that must reject the one submission, not abort the multi-tenant run."""
    subs = (
        _sub(0, _chain("A", [1.0, 2.0]), t=0.0, technique="ga",
             solver_options={"popsize": 8}),  # typo for pop_size
        _sub(1, _chain("B", [2.0, 1.0]), t=0.0, technique="heft"),
    )
    trace = Trace(name="typo", system=_two_node_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig(batch_window=0.5)).run(trace)
    assert [rec.status for rec in r.records] == ["rejected", "completed"]


def test_declined_batch_is_not_reported_as_batched():
    """When the technique's batch fn declines at runtime (per-instance-only
    backend option), submissions fall back to singles and nothing claims a
    batch happened."""
    opts = {"generations": 2, "pop_size": 8, "seed": 0, "backend": "pallas"}
    subs = tuple(
        _sub(i, _chain(f"D{i}", [1.0 + i, 2.0]), t=0.0, technique="ga",
             solver_options=opts)
        for i in range(2)
    )
    trace = Trace(name="decline", system=_two_node_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig(batch_window=0.5)).run(trace)
    assert [rec.status for rec in r.records] == ["completed", "completed"]
    assert r.batched_groups == 0 and r.batched_submissions == 0
    assert not any(rec.batched for rec in r.records)
    assert r.solver_calls == 2


def test_raising_batch_solve_is_recorded_then_retried_as_singles():
    """A batched solve that raises (say, a device compile failure of the
    whole sweep) still falls back to per-member solves, but what it raised is
    kept on the admission stats, the service result and a metrics counter —
    it must not pass for "nothing to batch"."""
    from repro.core.api import REGISTRY, SolverRegistry
    from repro.obs import METRICS, MetricsRegistry
    from repro.service.admission import AdmissionBatcher, PreparedSubmission
    from repro.service.cache import SolveCache

    def sweep_crash(problems, weights=None, **kw):
        raise RuntimeError("synthetic sweep crash")

    reg = SolverRegistry()
    reg.register("heft", REGISTRY.get("heft").fn, batch_fn=sweep_crash)
    subs = tuple(
        _sub(i, _chain(f"X{i}", [1.0 + i, 2.0]), t=0.0) for i in range(2)
    )
    trace = Trace(name="sweepcrash", system=_two_node_system(), submissions=subs)
    before = METRICS.snapshot()
    r = SchedulingService(trace.system, ServiceConfig(batch_window=0.5),
                          registry=reg).run(trace)
    delta = MetricsRegistry.delta(before, METRICS.snapshot())["counters"]
    assert [rec.status for rec in r.records] == ["completed", "completed"]
    assert r.batched_groups == 0 and r.solver_calls == 2
    assert r.batch_errors == ["RuntimeError: synthetic sweep crash"]
    assert r.summary()["batch_errors"] == r.batch_errors
    assert delta["service.admission.batch_errors"] == 1

    # the same record straight off the batcher's stats
    from repro.core.workload_model import Workload, build_problem

    preps = [
        PreparedSubmission(
            submission=s,
            problem=build_problem(trace.system, Workload((s.workflow,))),
            key=f"k{i}",
            baked={},
        )
        for i, s in enumerate(subs)
    ]
    stats = AdmissionBatcher(reg, SolveCache(16)).admit(preps)
    assert stats.batch_errors == ["RuntimeError: synthetic sweep crash"]
    assert all(p.schedule is not None for p in preps)


def test_service_config_rejects_degenerate_knobs():
    with pytest.raises(ValueError, match="max_batch"):
        ServiceConfig(max_batch=0)  # would spin the admit loop forever
    with pytest.raises(ValueError, match="batch_window"):
        ServiceConfig(batch_window=-1.0)
    with pytest.raises(ValueError, match="cache_capacity"):
        ServiceConfig(cache_capacity=0)


def test_unknown_node_in_trace_event_fails_fast():
    trace = Trace(
        name="badnode",
        system=_single_node_system(),
        submissions=(_sub(0, _chain("C", [1.0]), t=1.0),),
        events=(NodeEvent(time=0.0, kind="node-failure", node="N9"),),
    )
    with pytest.raises(ValueError, match="unknown node 'N9'"):
        SchedulingService(trace.system, ServiceConfig()).run(trace)


def test_duplicate_submission_ids_fail_fast():
    subs = (_sub(0, _chain("A", [1.0]), t=0.0), _sub(0, _chain("B", [2.0]), t=1.0))
    trace = Trace(name="dupid", system=_single_node_system(), submissions=subs)
    with pytest.raises(ValueError, match="duplicate submission id"):
        SchedulingService(trace.system, ServiceConfig()).run(trace)


def test_generated_node_events_target_the_embedded_system():
    """node_events=True must emit events consumable by serve_trace even for
    a custom system (targets drawn from the embedded nodes)."""
    system = _two_node_system()
    trace = generate_trace(
        6, seed=1, families=("random",), system=system, node_events=True,
    )
    assert {e.node for e in trace.events} <= {"N1", "N2"}
    r = SchedulingService(trace.system, ServiceConfig()).run(trace)  # no raise
    assert len(r.records) == 6


def test_coalesced_twin_of_rejected_solve_is_not_a_cache_hit():
    """Identical infeasible submissions in one window: the representative's
    invalid solve is never cached, so its twin must count as a miss (and be
    rejected), keeping hit_rate consistent with solver work skipped."""
    wf = Workflow("needs-f2", (Task("T0", features=frozenset({"F2"})),))
    subs = (_sub(0, wf, t=0.0), _sub(1, wf, t=0.0))
    trace = Trace(name="twin-rej", system=_single_node_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig(batch_window=1.0)).run(trace)
    assert [rec.status for rec in r.records] == ["rejected", "rejected"]
    assert not any(rec.cache_hit for rec in r.records)
    assert r.cache["hits"] == 0 and r.cache["misses"] == 2


def test_max_batch_overflow_readmits_in_order():
    subs = tuple(_sub(i, mri_w1(), t=0.0) for i in range(5))
    trace = Trace(name="overflow", system=continuum_system(), submissions=subs)
    r = SchedulingService(
        trace.system, ServiceConfig(batch_window=0.5, max_batch=2)
    ).run(trace)
    assert all(rec.status == "completed" for rec in r.records)
    admits = [e for e in r.event_log if e["kind"] == "admit"]
    assert len(admits) >= 3  # 5 submissions / max_batch 2


# ---------------------------------------------------------------------------
# monitor feedback, drift, failures
# ---------------------------------------------------------------------------

def test_drift_invalides_cache_and_model_converges():
    """After a node-drift event the next identical submission must MISS the
    cache (content key changed via the refreshed model) and its prediction
    must match observation (monitor learned the true speed)."""
    wf = _chain("C", [2.0, 3.0, 1.0])
    subs = (_sub(0, wf, t=0.0), _sub(1, wf, t=50.0))
    trace = Trace(
        name="drift",
        system=_single_node_system(),
        submissions=subs,
        events=(NodeEvent(time=0.0, kind="node-drift", node="N1", factor=0.5),),
    )
    r = SchedulingService(trace.system, ServiceConfig()).run(trace)
    r0, r1 = r.records
    # first solve predicted the unperturbed model, observed 2x slower
    assert r0.observed_makespan == pytest.approx(2.0 * r0.predicted_makespan)
    # second submission: cache miss (model changed), converged prediction
    assert not r1.cache_hit
    assert r.solver_calls == 2
    assert r1.observed_makespan == pytest.approx(r1.predicted_makespan)
    assert r1.predicted_makespan == pytest.approx(2.0 * r0.predicted_makespan)


def test_node_failure_routes_around_and_recovery_restores():
    wf = _chain("C", [2.0, 1.0])
    subs = (_sub(0, wf, t=1.0), _sub(1, wf, t=30.0))
    trace = Trace(
        name="fail",
        system=_two_node_system(),
        submissions=subs,
        events=(
            NodeEvent(time=0.0, kind="node-failure", node="N2"),
            NodeEvent(time=20.0, kind="node-recovery", node="N2"),
        ),
    )
    r = SchedulingService(trace.system, ServiceConfig()).run(trace)
    assert [rec.status for rec in r.records] == ["completed", "completed"]
    nodes_used = {
        e["id"]: set()
        for e in r.event_log if e["kind"] == "dispatch"
    }
    for e in r.event_log:
        if e["kind"] == "task-finished":
            nodes_used[e["id"]].add(e["node"])
    # while N2 was down, everything ran on N1
    assert nodes_used["s000"] == {"N1"}
    # after recovery, the 4x faster N2 is used again
    assert "N2" in nodes_used["s001"]
    # the failure also invalidated the cached solve (different feasibility)
    assert r.solver_calls == 2


def test_infeasible_submission_rejected_not_crashing():
    wf = Workflow("needs-f2", (Task("T0", features=frozenset({"F2"})),))
    subs = (_sub(0, wf, t=0.0), _sub(1, _chain("ok", [1.0, 2.0]), t=1.0))
    trace = Trace(name="rej", system=_single_node_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig()).run(trace)
    assert r.records[0].status == "rejected"
    assert r.records[1].status == "completed"
    assert any(e["kind"] == "rejected" and e["id"] == "s000"
               for e in r.event_log)
    # makespans() maps rejected to None (not NaN), so replays compare equal
    r2 = SchedulingService(trace.system, ServiceConfig()).run(trace)
    assert r.makespans()["s000"] is None
    assert r.makespans() == r2.makespans()


def test_contention_delays_overlapping_tenants():
    """Two simultaneous submissions on a one-node continuum cannot overlap:
    the second waits for the first's reserved window (queueing delay)."""
    wf = _chain("C", [4.0, 4.0])
    subs = (_sub(0, wf, t=0.0), _sub(1, wf, t=0.0))
    trace = Trace(name="contend", system=_single_node_system(), submissions=subs)
    r = SchedulingService(trace.system, ServiceConfig(batch_window=0.5)).run(trace)
    r0, r1 = r.records
    assert r0.queue_delay == 0.0
    assert r1.queue_delay == pytest.approx(r0.observed_makespan)
    assert r1.turnaround > r0.turnaround


# ---------------------------------------------------------------------------
# fault tolerance: preemption, requeue/backoff, terminal failure
# ---------------------------------------------------------------------------

def test_event_loop_cancellation_skips_silently():
    loop = EventLoop()
    keep = loop.push(1.0, "keep")
    drop = loop.push(2.0, "drop")
    loop.push(3.0, "tail")
    assert loop.cancel(drop) is True
    assert loop.cancel(drop) is False  # idempotent
    assert len(loop) == 2
    kinds = [ev.kind for ev in loop.drain()]
    assert kinds == ["keep", "tail"]
    assert keep.seq not in loop._cancelled


def test_retry_backoff_doubles_then_caps():
    from repro.service import retry_backoff

    assert [retry_backoff(i, base=1.0, cap=10.0) for i in range(1, 6)] == [
        1.0, 2.0, 4.0, 8.0, 10.0,
    ]
    with pytest.raises(ValueError, match="attempt"):
        retry_backoff(0)


def test_release_drops_cancelled_occupancy_and_recover_does_not_resurrect():
    """Satellite regression: a failed node's frontier must stop reflecting
    cancelled work, keep the truncated busy time, and stay deflated across
    a recovery."""
    from repro.core.simulator import ExecutionReport, TaskLog
    from repro.service import ContinuumState

    st = ContinuumState(_single_node_system())
    rep = ExecutionReport(
        logs=[TaskLog("T0", 0, 0.0, 10.0, 10.0)],
        makespan=10.0, predicted_makespan=10.0, slowdown=1.0,
    )
    st.reserve(rep, t0=0.0, sid="s0")
    assert st.frontier["N1"] == 10.0
    st.fail("N1")
    lost, cancelled = st.release("s0", at=1.0)
    assert lost == pytest.approx(1.0) and cancelled == 1
    # only the really-elapsed second remains on the frontier...
    assert st.frontier["N1"] == pytest.approx(1.0)
    st.recover("N1")
    # ...and recovery must not resurrect the cancelled window
    assert st.frontier["N1"] == pytest.approx(1.0)
    assert st.busy_seconds["N1"] == pytest.approx(1.0)
    # releasing an unknown/already-released sid is a no-op
    assert st.release("s0", at=5.0) == (0.0, 0)


def test_midrun_failure_preempts_salvages_and_completes_after_recovery():
    """The tentpole end to end on one node: failure mid-task cancels the
    stale completion, salvages the finished prefix, requeues the remainder
    with backoff, and the submission completes after recovery."""
    wf = _chain("C", [2.0, 2.0, 2.0])  # runs [0.25,2.25][2.25,4.25][4.25,6.25]
    trace = Trace(
        name="preempt",
        system=_single_node_system(),
        submissions=(_sub(0, wf, t=0.0),),
        events=(
            NodeEvent(time=3.0, kind="node-failure", node="N1"),
            NodeEvent(time=10.0, kind="node-recovery", node="N1"),
        ),
    )
    cfg = ServiceConfig(max_retries=5, backoff_base=1.0, backoff_cap=8.0)
    r = SchedulingService(trace.system, cfg).run(trace)
    rec = r.records[0]
    assert rec.status == "completed"
    assert rec.retries >= 2  # preemption + transient infeasibility while down
    assert rec.rescheduled_tasks == 2  # T1 (mid-flight) and T2 (future)
    assert rec.lost_work_seconds == pytest.approx(0.75)  # T1 ran 2.25→3.0
    pre = [e for e in r.event_log if e["kind"] == "preempted"]
    assert len(pre) == 1
    assert pre[0]["salvaged"] == 1 and pre[0]["rescheduled"] == 2
    # the pre-computed completion for t=6.25 was cancelled: exactly one
    # completion fires, after the recovery
    comps = [e for e in r.event_log if e["kind"] == "completion"]
    assert len(comps) == 1 and comps[0]["time"] > 10.0
    assert any(e["kind"] == "requeue" for e in r.event_log)
    # stretch metrics surface in the summary
    s = r.summary()
    assert s["robustness"]["retries"] == rec.retries
    assert s["robustness"]["lost_work_seconds"] == pytest.approx(0.75)
    assert s["robustness"]["makespan_stretch"]["mean"] > 1.0
    # and the chaos path stays replayable
    r2 = SchedulingService(trace.system, cfg).run(trace)
    assert r.event_log == r2.event_log
    assert r.makespans() == r2.makespans()


def test_preemption_releases_dead_node_occupancy_for_later_tenants():
    """Satellite regression at the service level: with the preempted work
    terminally failed (max_retries=0), a later submission must see a
    frontier reflecting only the salvaged second, not the cancelled ten."""
    a = Workflow("long", (Task("T0", cores=2, work=10.0,
                               features=frozenset({"F1"})),))
    b = _chain("B", [1.0])
    trace = Trace(
        name="stale-occ",
        system=_single_node_system(),
        submissions=(_sub(0, a, t=0.0), _sub(1, b, t=5.0)),
        events=(
            NodeEvent(time=1.0, kind="node-failure", node="N1"),
            NodeEvent(time=2.0, kind="node-recovery", node="N1"),
        ),
    )
    r = SchedulingService(
        trace.system, ServiceConfig(max_retries=0)
    ).run(trace)
    ra, rb = r.records
    assert ra.status == "failed"
    assert "retry budget exhausted" in ra.reason
    assert r.makespans()["s000"] is None
    # stale occupancy would have forced rb to wait until t≈10.25
    assert rb.status == "completed"
    assert rb.queue_delay == 0.0
    assert any(e["kind"] == "failed" and e["id"] == "s000"
               for e in r.event_log)
    assert r.summary()["failed"] == 1


def test_failure_before_admission_retries_until_recovery():
    """A submission whose admission window opens during a full outage is
    transiently infeasible: it must back off and complete post-recovery
    instead of being rejected."""
    trace = Trace(
        name="down-at-admit",
        system=_single_node_system(),
        submissions=(_sub(0, _chain("C", [1.0, 1.0]), t=0.5),),
        events=(
            NodeEvent(time=0.0, kind="node-failure", node="N1"),
            NodeEvent(time=4.0, kind="node-recovery", node="N1"),
        ),
    )
    r = SchedulingService(
        trace.system, ServiceConfig(max_retries=5, backoff_base=1.0)
    ).run(trace)
    rec = r.records[0]
    assert rec.status == "completed"
    assert rec.retries > 0
    assert rec.rescheduled_tasks == 0  # never dispatched before the outage
    assert not any(e["kind"] == "rejected" for e in r.event_log)


def test_retry_budget_exhaustion_is_terminal_failed_with_reason():
    trace = Trace(
        name="budget",
        system=_single_node_system(),
        submissions=(_sub(0, _chain("C", [4.0]), t=0.0),),
        events=(NodeEvent(time=1.0, kind="node-failure", node="N1"),),
    )
    r = SchedulingService(
        trace.system, ServiceConfig(max_retries=1, backoff_base=0.5)
    ).run(trace)
    rec = r.records[0]
    assert rec.status == "failed"
    assert "retry budget exhausted (1)" in rec.reason
    assert math.isnan(rec.observed_makespan)
    assert rec.finished > 0 and rec.turnaround > 0
    json.dumps(rec.to_json(), allow_nan=False)  # still strict JSON
    fails = [e for e in r.event_log if e["kind"] == "failed"]
    assert len(fails) == 1 and fails[0]["reason"] == rec.reason


def test_drift_after_dispatch_does_not_rewrite_inflight_work():
    """Drift lands between dispatch and completion: the in-flight execution
    keeps its dispatch-time speeds; only later submissions see the change."""
    wf = _chain("C", [2.0, 2.0])
    trace = Trace(
        name="drift-mid",
        system=_single_node_system(),
        submissions=(_sub(0, wf, t=0.0), _sub(1, wf, t=30.0)),
        events=(NodeEvent(time=1.0, kind="node-drift", node="N1", factor=0.5),),
    )
    r = SchedulingService(trace.system, ServiceConfig()).run(trace)
    r0, r1 = r.records
    assert r0.status == r1.status == "completed"
    # in-flight work unaffected (model and truth agreed at dispatch time)
    assert r0.observed_makespan == pytest.approx(r0.predicted_makespan)
    # the later tenant executes at the drifted speed: twice as slow as the
    # (not yet converged) model predicts
    assert r1.observed_makespan == pytest.approx(2.0 * r1.predicted_makespan)


def test_set_drift_rejects_nonpositive_factors():
    from repro.service import ContinuumState

    st = ContinuumState(_single_node_system())
    for bad in (0.0, -1.0, float("nan")):
        with pytest.raises(ValueError, match="drift factor"):
            st.set_drift("N1", bad)
    # and the service fails fast at run() on a bad trace event
    trace = Trace(
        name="bad-drift",
        system=_single_node_system(),
        submissions=(_sub(0, _chain("C", [1.0]), t=1.0),),
        events=(NodeEvent(time=0.0, kind="node-drift", node="N1", factor=0.0),),
    )
    with pytest.raises(ValueError, match="factor > 0"):
        SchedulingService(trace.system, ServiceConfig()).run(trace)


def test_unexpected_solver_exception_rejects_with_recorded_error():
    """An arbitrary (non-ValueError/TypeError) solver crash must reject the
    one submission with a recorded reason, not abort the run."""
    from repro.core.api import REGISTRY, SolverRegistry
    from repro.core.evaluator import ObjectiveWeights

    reg = SolverRegistry()

    def boom(problem, weights=ObjectiveWeights(), **kw):
        raise RuntimeError("synthetic solver crash")

    reg.register("boom", boom)
    reg.register("heft", REGISTRY.get("heft").fn)
    subs = (
        _sub(0, _chain("A", [1.0, 2.0]), t=0.0, technique="boom"),
        _sub(1, _chain("B", [2.0, 1.0]), t=0.0, technique="heft"),
    )
    trace = Trace(name="crash", system=_two_node_system(), submissions=subs)
    svc = SchedulingService(trace.system, ServiceConfig(batch_window=0.5),
                            registry=reg)
    r = svc.run(trace)
    assert [rec.status for rec in r.records] == ["rejected", "completed"]
    assert r.records[0].reason == "RuntimeError: synthetic solver crash"


def test_fallback_chain_completes_submission_via_degraded_technique():
    from repro.core.api import REGISTRY, SolverRegistry
    from repro.core.evaluator import ObjectiveWeights

    reg = SolverRegistry()

    def boom(problem, weights=ObjectiveWeights(), **kw):
        raise RuntimeError("synthetic solver crash")

    reg.register("boom", boom)
    reg.register("heft", REGISTRY.get("heft").fn)
    trace = Trace(
        name="fallback",
        system=_two_node_system(),
        submissions=(_sub(0, _chain("A", [1.0, 2.0]), t=0.0, technique="boom"),),
    )
    svc = SchedulingService(
        trace.system, ServiceConfig(fallback=("heft",)), registry=reg
    )
    r = svc.run(trace)
    rec = r.records[0]
    assert rec.status == "completed"
    assert rec.technique_used == "heft"
    assert rec.fallbacks and rec.fallbacks[0].startswith("boom:RuntimeError")


def test_chaos_trace_zero_silently_lost_and_bit_identical_replay():
    """Acceptance: a chaos trace with mid-run failures ends every record in
    a terminal status (with a reason when not completed) and replays
    bit-identically at the fixed seed."""
    trace = generate_trace(
        20, seed=3, rate=2.0,
        chaos={"horizon": 400.0, "failure_rate": 0.02, "outage_mean": 30.0,
               "drift_rate": 0.02},
    )
    assert any(e.kind == "node-failure" for e in trace.events)
    cfg = ServiceConfig(batch_window=0.5, seed=3, max_retries=3,
                        backoff_base=0.5, backoff_cap=16.0)
    a = SchedulingService(trace.system, cfg).run(trace)
    b = SchedulingService(trace.system, cfg).run(trace)
    assert a.event_log == b.event_log
    assert a.makespans() == b.makespans()
    assert [r.to_json() for r in a.records] == [r.to_json() for r in b.records]
    for rec in a.records:
        assert rec.status in ("completed", "rejected", "failed")
        if rec.status != "completed":
            assert rec.reason or any(
                e["kind"] == "rejected" and e["id"] == rec.id
                for e in a.event_log
            )
    # summary totals account for every submission
    s = a.summary()
    assert s["completed"] + s["rejected"] + s["failed"] == len(a.records)
    json.dumps(s, allow_nan=False)  # strict JSON including new metric blocks


def test_service_config_rejects_degenerate_fault_knobs():
    with pytest.raises(ValueError, match="max_retries"):
        ServiceConfig(max_retries=-1)
    with pytest.raises(ValueError, match="backoff_base"):
        ServiceConfig(backoff_base=0.0)
    with pytest.raises(ValueError, match="backoff_cap"):
        ServiceConfig(backoff_cap=0.0)
    with pytest.raises(ValueError, match="solve_budget"):
        ServiceConfig(solve_budget=0.0)


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------

def test_trace_json_roundtrip_bit_exact(tmp_path):
    trace = generate_trace(10, seed=4, node_events=True)
    obj = trace.to_json()
    assert trace_from_json(json.loads(json.dumps(obj))).to_json() == obj
    p = trace.save(tmp_path / "trace.json")
    assert load_trace(p).to_json() == obj


def test_generated_trace_arrivals_sorted_and_families_valid():
    trace = generate_trace(50, seed=9)
    times = [s.time for s in trace.submissions]
    assert times == sorted(times)
    assert {s.family for s in trace.submissions} <= {"mri", "stgs", "random", "tpu"}
    assert len({s.id for s in trace.submissions}) == 50


def test_service_summary_is_json_serializable():
    trace = generate_trace(5, seed=1, families=("mri",))
    r = SchedulingService(trace.system, ServiceConfig()).run(trace)
    obj = json.loads(json.dumps(r.summary()))
    assert obj["submissions"] == 5
    assert obj["completed"] + obj["rejected"] == 5
    assert 0.0 <= obj["cache"]["hit_rate"] <= 1.0


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _repro_env():
    return {
        "PYTHONPATH": str(Path(__file__).resolve().parents[1] / "src"),
        "PATH": "/usr/bin:/bin",
        "JAX_PLATFORMS": "cpu",
    }


def test_cli_trace_and_serve(tmp_path):
    trace_path = tmp_path / "trace.json"
    out_path = tmp_path / "result.json"
    gen = subprocess.run(
        [sys.executable, "-m", "repro", "trace", str(trace_path),
         "-n", "6", "--seed", "3", "--families", "mri,tpu"],
        capture_output=True, text=True, env=_repro_env(),
    )
    assert gen.returncode == 0, gen.stderr
    assert trace_path.exists()
    serve = subprocess.run(
        [sys.executable, "-m", "repro", "serve", str(trace_path),
         "--jitter", "0.05", "--seed", "7", "--out", str(out_path)],
        capture_output=True, text=True, env=_repro_env(),
    )
    assert serve.returncode == 0, serve.stderr
    summary = json.loads(serve.stdout)
    assert summary["submissions"] == 6
    assert summary["completed"] == 6
    assert json.loads(out_path.read_text()) == summary
