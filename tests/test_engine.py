"""`repro.engine` acceptance suite (the PR 4 tentpole):

* cross-backend equivalence — a randomized (hypothesis-guarded) sweep
  asserting the oracle-f32, jax, and pallas-interpret backends return
  bit-identical makespans/violations on the same ``PackedProblem``;
* the one simulator — ``engine.sim`` reproduces HEFT's schedules and the
  service's truth-execution finish times exactly (executor replay with no
  perturbation == oracle timing, bit for bit);
* pack cache — fingerprint-keyed LRU: content-identical rebuilds reuse the
  padded arrays and device buffers; the service surfaces the hit rate;
* registry — capability metadata, plugin registration, alias resolution,
  and Scenario-level engine selection.
"""

import numpy as np
import pytest

from repro.core import (
    ObjectiveWeights,
    Scenario,
    Workload,
    build_problem,
    mri_system,
    mri_workload,
    run_scenario,
    scenario_from_json,
    synthetic_system,
)
from repro.core.evaluator import evaluate_assignment
from repro.core.heuristics import heft, olb
from repro.core.simulator import execute
from repro.core.workload_model import random_layered_workflow
from repro.engine import (
    ENGINES,
    EngineCapabilities,
    EngineRegistry,
    PackedProblem,
    ScheduleEngine,
    bucket_of,
    pack,
    pack_cache,
)
from repro.engine.sim import CoreSim, ready_times_all, run_schedule

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # container without hypothesis: keep the suite runnable
    HAVE_HYPOTHESIS = False


def _random_problem(seed: int, tasks: int, nodes: int, max_cores: int = 8):
    system = synthetic_system(nodes, seed=seed)
    wf = random_layered_workflow(tasks, seed=seed, max_cores=max_cores, comm=True)
    return build_problem(system, Workload((wf,)))


# -----------------------------------------------------------------------------
# cross-backend bit-for-bit equivalence
# -----------------------------------------------------------------------------


def _assert_backends_agree(problem, seed: int, pop: int = 6):
    rng = np.random.default_rng(seed)
    A = rng.integers(0, problem.num_nodes, (pop, problem.num_tasks))
    packed = pack(problem)
    results = {}
    for name in ("oracle", "jax", "pallas"):
        eng = ENGINES.get(name)
        assert eng.capabilities.exact_f32
        # jax/pallas consume the canonical PackedProblem directly; the
        # oracle walks the raw problem — same model, same bits
        target = problem if name == "oracle" else packed
        _, mk = eng.population_fitness(target, ObjectiveWeights())(A)
        results[name] = np.asarray(mk).astype(np.float32)
    np.testing.assert_array_equal(results["oracle"], results["jax"])
    np.testing.assert_array_equal(results["oracle"], results["pallas"])
    # violations agree with the oracle count
    for k in range(pop):
        s32 = evaluate_assignment(problem, A[k], dtype=np.float32)
        assert np.float32(s32.makespan) == results["oracle"][k]


@pytest.mark.parametrize("seed,tasks,nodes", [(0, 7, 3), (1, 13, 4), (2, 21, 5)])
def test_cross_backend_bit_for_bit_fixed(seed, tasks, nodes):
    _assert_backends_agree(_random_problem(seed, tasks, nodes), seed)


def test_cross_backend_bit_for_bit_mri():
    _assert_backends_agree(build_problem(mri_system(), mri_workload()), 123)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        tasks=st.integers(3, 18),
        nodes=st.integers(2, 5),
        max_cores=st.sampled_from([2, 4, 8]),
    )
    def test_cross_backend_bit_for_bit_randomized(seed, tasks, nodes, max_cores):
        problem = _random_problem(seed, tasks, nodes, max_cores)
        _assert_backends_agree(problem, seed, pop=4)


# -----------------------------------------------------------------------------
# one simulator: heuristics and truth execution share engine.sim
# -----------------------------------------------------------------------------


def test_sim_reproduces_oracle_timing_bit_for_bit():
    problem = _random_problem(5, 15, 4)
    rng = np.random.default_rng(5)
    A = rng.integers(0, problem.num_nodes, problem.num_tasks)
    start, finish, violations = run_schedule(problem, A)
    sched = evaluate_assignment(problem, A)
    np.testing.assert_array_equal(start, sched.start)
    np.testing.assert_array_equal(finish, sched.finish)
    assert violations == sched.violations


def _walk_per_task(problem, assignment, dtype, speed_factors=None, jitter_mults=None):
    """The replay task by task in numpy arrays (sorted core rows, the
    predecessors' terms gathered per task): the formulation that
    ``run_schedule``'s walk over scalars has to reproduce bit for bit."""
    durations = problem.durations
    if speed_factors is not None:
        durations = durations / np.maximum(speed_factors, 1e-9)[None, :]
    durations, data, dtr = (a.astype(dtype) for a in (durations, problem.data, problem.dtr))
    release, inf = problem.release.astype(dtype), dtype(1e30)
    rows = [np.zeros(max(int(c), 1), dtype) for c in problem.node_cores]
    T = problem.num_tasks
    start, finish = np.zeros(T, dtype), np.zeros(T, dtype)
    indptr, indices = problem.pred_csr
    for j in range(T):
        i = int(assignment[j])
        ready = release[j]
        ps = indices[indptr[j] : indptr[j + 1]]
        if ps.size:
            ips = assignment[ps]
            rates = dtr[ips, i]
            ok = np.isfinite(rates) & (rates > 0)
            with np.errstate(divide="ignore", invalid="ignore"):
                transfer = np.where(ips == i, dtype(0.0),
                                    np.where(ok, data[ps] / np.where(ok, rates, 1), inf))
            ready = np.maximum(ready, (finish[ps] + transfer).max())
        row = rows[i]
        c = int(max(1, min(problem.cores[j], row.size)))
        s = np.maximum(ready, row[c - 1])
        dur = durations[j, i] if jitter_mults is None else durations[j, i] * jitter_mults[j]
        f = s + dur
        rows[i] = np.sort(np.concatenate([row[c:], np.full(c, f, dtype)]))
        start[j], finish[j] = s, f
    violations = int(sum(not problem.feasible[j, assignment[j]] for j in range(T)))
    return start, finish, violations


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("perturb", ["none", "jitter", "speed", "both"])
def test_run_schedule_walk_matches_per_task_arrays(dtype, perturb):
    """The scalar walk (edge transfers computed up front) gives the per-task
    array formulation's starts, finishes and violations bit for bit, also
    over dead links, a node without cores and infeasible placements."""
    import dataclasses

    base = _random_problem(17, 40, 6)
    dtr = base.dtr.copy()
    dtr[0, 1], dtr[2, 3] = np.inf, 0.0  # dead links
    node_cores = base.node_cores.copy()
    node_cores[4] = 0
    problem = dataclasses.replace(base, dtr=dtr, node_cores=node_cores)
    rng = np.random.default_rng(17)
    A = rng.integers(0, problem.num_nodes, problem.num_tasks)
    kw = {}
    if perturb in ("speed", "both"):
        kw["speed_factors"] = rng.uniform(0.5, 2.0, problem.num_nodes)
    if perturb in ("jitter", "both"):
        kw["jitter_mults"] = rng.lognormal(0.0, 0.3, problem.num_tasks)
    got = run_schedule(problem, A, dtype=dtype, **kw)
    want = _walk_per_task(problem, A, dtype, **kw)
    for g, w in zip(got[:2], want[:2]):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    assert got[2] == want[2] > 0


def test_truth_execution_matches_oracle_exactly():
    """The service's truth executor replays through engine.sim — with no
    perturbation its finish times are the oracle's, bit for bit."""
    problem = build_problem(mri_system(), mri_workload())
    sched = heft(problem)
    report = execute(problem, sched)
    finishes = np.array([log.finish for log in report.logs])
    np.testing.assert_array_equal(finishes, sched.finish)
    assert report.makespan == sched.makespan
    assert report.slowdown == 1.0


def test_heft_greedy_state_equals_oracle_rescore():
    """HEFT's incremental CoreSim bookkeeping must agree with the oracle's
    re-evaluation of its own assignment (identical semantics, one sim)."""
    for seed, tasks, nodes in [(3, 12, 3), (7, 25, 5)]:
        problem = _random_problem(seed, tasks, nodes)
        for solver in (heft, olb):
            sched = solver(problem)
            re = evaluate_assignment(problem, sched.assignment)
            assert sched.makespan == re.makespan
            assert sched.violations == re.violations


def test_coresim_kth_and_commit_track_a_naive_model():
    problem = _random_problem(11, 6, 3)
    sim = CoreSim(problem, exact=True)
    naive = [np.zeros(max(int(c), 1)) for c in sim.caps]
    rng = np.random.default_rng(11)
    t = 0.0
    for _ in range(50):
        i = int(rng.integers(0, problem.num_nodes))
        c = int(rng.integers(1, max(int(sim.caps[i]), 1) + 1))
        t += float(rng.random())
        idx = np.argsort(naive[i], kind="stable")[:c]
        expect = naive[i][idx[-1]]
        assert sim.kth_free(i, c) == expect
        naive[i][idx] = t
        sim.commit(i, c, t)


def test_ready_times_all_matches_scalar_path():
    problem = _random_problem(13, 14, 4)
    rng = np.random.default_rng(13)
    A = rng.integers(0, problem.num_nodes, problem.num_tasks)
    _, finish, _ = run_schedule(problem, A)
    indptr, indices = problem.pred_csr
    for j in range(problem.num_tasks):
        ready = ready_times_all(problem, j, A, finish)
        assert ready.shape == (problem.num_nodes,)
        # the f32 factor path agrees with the exact division path closely
        ps = indices[indptr[j] : indptr[j + 1]]
        for i in range(problem.num_nodes):
            exact = problem.release[j]
            for p in ps:
                rate = problem.dtr[int(A[p]), i]
                tt = 0.0 if int(A[p]) == i else float(problem.data[p]) / rate
                exact = max(exact, float(finish[p]) + tt)
            assert ready[i] == pytest.approx(exact, rel=1e-5, abs=1e-4)


# -----------------------------------------------------------------------------
# pack cache
# -----------------------------------------------------------------------------


def test_pack_cache_hits_on_content_identical_rebuild():
    system = synthetic_system(3, seed=31)
    wf = random_layered_workflow(9, seed=31, max_cores=4)
    p1 = build_problem(system, Workload((wf,)))
    p2 = build_problem(system, Workload((wf,)))  # fresh arrays, same content
    stats = pack_cache().stats
    h0, m0, _ = stats.snapshot()
    packed1 = pack(p1)
    packed2 = pack(p2)
    h1, m1, _ = stats.snapshot()
    assert packed2 is packed1  # one PackedProblem serves both builds
    assert h1 - h0 >= 1
    assert m1 - m0 <= 1
    # device buffers are cached on the shared instance: one transfer total
    assert packed1.device_arrays()["durations"] is packed2.device_arrays()["durations"]


def test_pack_is_read_only_and_padding_is_neutral():
    problem = _random_problem(17, 10, 3)
    packed = pack(problem)
    assert isinstance(packed, PackedProblem)
    assert packed.bucket == bucket_of(problem)
    with pytest.raises(ValueError):
        packed.durations[0, 0] = 1.0  # read-only canonical arrays
    # real region round-trips exactly
    T, N = problem.num_tasks, problem.num_nodes
    np.testing.assert_array_equal(
        packed.durations[:T, :N], problem.durations.astype(np.float32)
    )
    assert packed.feasible[T:, 0].all()
    assert not packed.feasible[:T, N:].any()


def test_pack_rejects_too_small_bucket():
    problem = _random_problem(19, 12, 3)
    with pytest.raises(ValueError, match="exceeds bucket"):
        pack(problem, (4, 4, 4, 1, 4))


def test_pack_cache_is_byte_bounded():
    from repro.engine.packed import PackCache

    problems = [_random_problem(40 + s, 8, 3) for s in range(4)]
    sizes = [pack(p, use_cache=False).nbytes for p in problems]
    cache = PackCache(capacity=64, max_bytes=int(sum(sizes[:2]) + sizes[2] // 2))
    for i, p in enumerate(problems[:3]):
        cache.get_or_build(("k", i), lambda p=p: pack(p, use_cache=False))
    assert cache.retained_bytes <= cache.max_bytes  # evicted down to budget
    assert len(cache) < 3
    # an entry larger than the whole budget is served but never retained
    tiny = PackCache(capacity=64, max_bytes=16)
    built = tiny.get_or_build(("big",), lambda: pack(problems[0], use_cache=False))
    assert built.nbytes > tiny.max_bytes
    assert len(tiny) == 0 and tiny.retained_bytes == 0


def test_service_surfaces_pack_cache_hit_rate():
    from repro.service import ServiceConfig, generate_trace, serve_trace

    trace = generate_trace(24, seed=3, rate=6.0, families=("mri",))
    result = serve_trace(trace, config=ServiceConfig(batch_window=0.5, seed=3))
    assert set(result.pack_cache) >= {"hits", "misses", "hit_rate"}
    assert result.summary()["pack_cache"] == result.pack_cache


def test_pack_reused_across_solve_cache_misses():
    """The satellite scenario: resubmitting the same workflow with different
    solve parameters misses the *solve* cache (new key) but must hit the
    *pack* LRU (same problem fingerprint) — no re-pad, no re-transfer."""
    from repro.core.workload_model import mri_w1
    from repro.service import ServiceConfig, SchedulingService, Trace
    from repro.service.traces import Submission

    opts = {"pop_size": 8, "generations": 3}
    subs = tuple(
        Submission(
            id=f"s{k}", tenant="t", time=0.1 * k, family="mri", workflow=mri_w1(),
            technique="ga", solver_options={**opts, "seed": k},  # distinct solve keys
        )
        for k in range(3)
    )
    trace = Trace(name="pack-reuse", system=mri_system(), submissions=subs, events=())
    pack_cache().clear()  # absolute hit/miss assertions below need an empty LRU
    # batch_window=0 admits each submission alone: three separate GA solves
    service = SchedulingService(trace.system, ServiceConfig(batch_window=0.0))
    result = service.run(trace)
    assert all(r.status == "completed" for r in result.records)
    assert not any(r.cache_hit for r in result.records)  # solve keys differ
    assert result.solver_calls == 3
    # ... but the problem content is identical: one pack, two reuses.
    # (The monitor converges to factor 1.0 with no perturbation, so the
    # rebuilt problems stay fingerprint-identical across admissions.)
    assert result.pack_cache["misses"] == 1
    assert result.pack_cache["hits"] == 2
    assert result.pack_cache["hit_rate"] > 0.6


def test_generated_stgs_trace_warms_pack_cache():
    """Pin the trace-generator behavior that makes the pack LRU observable:
    stgs submissions vary their GA seed per tenant, so content-identical
    resubmissions miss the *solve* cache (distinct option keys) yet reuse
    the fingerprint-keyed *pack*.  Before this, every repeat carried the
    same options, was absorbed by the solve cache before reaching a solver,
    and the service lane reported pack hit_rate == 0.0 forever."""
    from repro.service import ServiceConfig, generate_trace, serve_trace

    pack_cache().clear()
    # stgs only: three distinct workflows across 24 submissions, so repeated
    # content is certain; seeds drawn from {0..3} guarantee repeated
    # (workflow, options) pairs never all collapse into the solve cache
    trace = generate_trace(24, seed=5, rate=6.0, families=("stgs",))
    result = serve_trace(trace, config=ServiceConfig(batch_window=0.5, seed=5))
    assert all(r.status == "completed" for r in result.records)
    assert result.pack_cache["hits"] > 0
    assert 0.0 < result.pack_cache["hit_rate"] <= 1.0


# -----------------------------------------------------------------------------
# registry + scenario-level engine selection
# -----------------------------------------------------------------------------


def test_registry_metadata_and_aliases():
    assert set(ENGINES.names()) >= {"oracle", "jax", "pallas"}
    assert ENGINES.get("jnp") is ENGINES.get("jax")  # legacy alias
    assert ENGINES.get("numpy") is ENGINES.get("oracle")
    assert ENGINES.get("auto").name in ("jax", "pallas")
    assert ENGINES.capabilities("jax").supports_batch
    assert not ENGINES.capabilities("oracle").supports_batch
    with pytest.raises(KeyError, match="unknown engine"):
        ENGINES.get("warp-drive")


def test_plugin_engine_registers_and_routes():
    reg = EngineRegistry()

    from repro.engine import register_engine

    @register_engine("twice-oracle", registry=reg)
    class TwiceOracle(ScheduleEngine):
        capabilities = EngineCapabilities(supports_population=True)

        def population_fitness(self, problem, weights=None, *, core_cap=None):
            base = ENGINES.get("oracle").population_fitness(problem, weights)

            def fitness(assignments):
                obj, mk = base(assignments)
                return obj * 2.0, mk

            return fitness

    problem = _random_problem(23, 6, 3)
    A = np.random.default_rng(23).integers(0, problem.num_nodes, (3, problem.num_tasks))
    obj2, mk2 = reg.get("twice-oracle").population_fitness(problem)(A)
    obj1, mk1 = ENGINES.get("oracle").population_fitness(problem)(A)
    np.testing.assert_array_equal(np.asarray(mk2), np.asarray(mk1))
    np.testing.assert_allclose(np.asarray(obj2), 2.0 * np.asarray(obj1))
    with pytest.raises(ValueError, match="already registered"):
        reg.register("twice-oracle", TwiceOracle)


def test_scenario_engine_field_round_trips_and_routes():
    import json

    sc = Scenario(
        name="engine-routing",
        system=mri_system(),
        workload=mri_workload(),
        technique="ga",
        engine="pallas",
        solver_options={"pop_size": 8, "generations": 4},
        orchestration=__import__("repro.core.api", fromlist=["OrchestrationConfig"]).OrchestrationConfig(max_rounds=1),
    )
    obj = sc.to_json()
    assert obj["scenario"]["engine"] == "pallas"
    rt = scenario_from_json(json.loads(json.dumps(obj)))
    assert rt.engine == "pallas"
    assert rt.to_json() == obj  # bit-exact round trip with the new field
    result = run_scenario(sc)
    assert result.final_schedule.technique == "ga"
    assert result.final_schedule.violations == 0


def test_engine_selection_never_leaks_into_exact_solvers():
    """A scenario pinning engine=pallas with auto routing must still be able
    to fall back to MILP/HEFT (they never see a backend kwarg)."""
    from repro.core.api import route_problem

    problem = build_problem(mri_system(), mri_workload())
    rep = route_problem(problem, technique="auto", engine="pallas")
    assert rep.schedule.violations == 0


# -----------------------------------------------------------------------------
# the ``fitness`` scope in the device programs
# -----------------------------------------------------------------------------


def _sweep_program_inputs(family: str = "layered"):
    import jax

    from repro.core import montage_workflow
    from repro.engine import stack_packed

    system = synthetic_system(3, seed=3)
    if family == "montage":  # joins of 110 and 89 fits: several rows a join
        workflows = [montage_workflow(6, 6, seed=1), montage_workflow(5, 6, seed=2)]
    else:
        workflows = [random_layered_workflow(10, seed=100 + i, max_cores=4) for i in range(2)]
    problems = [build_problem(system, Workload((wf,))) for wf in workflows]
    arrays, bucket = stack_packed(problems)
    logits = np.zeros((2, bucket[0], bucket[1]), np.float32)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(0), 2))
    return arrays, logits, keys, 1.0, 1.0, 0.08


def _fitness_fusions(compiled_text: str) -> list[str]:
    import re

    fusions = re.findall(r'^\s*(?:ROOT )?%?(\S*fusion\S*) = .*op_name="([^"]*)"',
                         compiled_text, re.M)
    return [name for name, op_name in fusions if "fitness" in op_name.split("/")]


def test_ga_sweep_program_carries_fitness_scope():
    """The compiled GA sweep names the evaluator's fused ops: their
    ``op_name`` has a ``fitness`` segment, which a profiler trace shows."""
    from repro.core.metaheuristics import _ga_sweep_core

    run = _ga_sweep_core("fixed", 8, 3, 4, 2)
    text = run.lower(*_sweep_program_inputs()).compile().as_text()
    assert _fitness_fusions(text)


@pytest.mark.parametrize("family", ["layered", "montage"])
def test_preds_scope_sits_inside_fitness(family):
    """A row's predecessor terms run under ``preds`` inside ``fitness``, with
    one row per task and with joins over several rows."""
    import re

    from repro.core.metaheuristics import _ga_sweep_core

    run = _ga_sweep_core("fixed", 8, 2, 4, 2)
    text = run.lower(*_sweep_program_inputs(family)).compile().as_text()
    # full paths only: a reduction's own region carries a relative name
    paths = [p.split("/") for p in re.findall(r'op_name="(jit\([^"]*)"', text)]
    preds = [p for p in paths if "preds" in p]
    assert preds
    for p in preds:
        assert any(re.fullmatch(r"(\w+\()*fitness\)*", seg) for seg in p[: p.index("preds")])


def test_fitness_scope_is_metadata_only(monkeypatch):
    """With and without the scope, the sweep compiles to the same program
    once metadata is stripped, and returns the same bits."""
    import contextlib
    import re

    import jax

    from repro.core.metaheuristics import _ga_sweep_one

    def compiled(scoped: bool):
        if not scoped:
            monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        one = _ga_sweep_one("fixed", 8, 3, 4, 2)
        program = jax.jit(jax.vmap(one, in_axes=(0, 0, 0, None, None, None)))
        inputs = _sweep_program_inputs()
        text = program.lower(*inputs).compile().as_text()
        out = [np.asarray(x) for x in program(*inputs)]
        monkeypatch.undo()
        return text, out

    (with_text, with_out), (bare_text, bare_out) = compiled(True), compiled(False)
    assert _fitness_fusions(with_text) and not _fitness_fusions(bare_text)

    def strip(text):  # the instructions alone: no metadata, no source table
        return re.sub(r", metadata=\{[^}]*\}", "", text.split("\nFileNames", 1)[0])

    assert strip(with_text) == strip(bare_text)
    for a, b in zip(with_out, bare_out):
        np.testing.assert_array_equal(a, b)
