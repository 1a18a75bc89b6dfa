"""Fast-path equivalence & batching tests (the PR-1 acceptance sweep):

* the shared rank-select primitive == numpy stable sort semantics,
* jnp fitness == Pallas kernel (interpret, resident AND streamed modes)
  == numpy f32 oracle, **bit-for-bit**, over randomized problem shapes
  (wide/narrow core windows, multi-core tasks, cross-node transfers),
* bucket padding in the batched multi-instance API never changes
  per-instance objectives,
* one XLA compile per shape bucket across repeated sweeps (Table IX sizes),
* the vmapped GA sweep emits valid schedules.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (
    Node,
    ObjectiveWeights,
    Workload,
    build_problem,
    evaluate_assignment,
    evaluate_population_batch,
    mri_system,
    mri_workload,
    synthetic_system,
    verify_schedule,
)
from repro.core.evaluator import make_fitness_fn
from repro.engine import bucket_of, fitness_cache_sizes, pack
from repro.core.system_model import make_system
from repro.core.workload_model import random_layered_workflow, synthetic_workload
from repro.kernels.makespan import population_makespan_pallas
from repro.kernels.select import kth_from_ranks, stable_ranks, update_from_ranks


def _narrow_system(num_nodes: int, cores: int = 2):
    """System whose nodes own very few cores — a narrow CMAX window."""
    nodes = [
        Node(
            f"n{i}",
            {"cores": cores, "memory": 64.0},
            frozenset({"F1", "F2"}),
            {"processing_speed": 1.0 + (i % 3), "data_transfer_rate": 10.0 * (1 + i % 2)},
        )
        for i in range(num_nodes)
    ]
    return make_system(nodes)


def _problems():
    """Shape sweep: MRI (wide 512-core window), synthetic heterogeneous
    (multi-core tasks + cross-node transfers), narrow 2-core nodes."""
    out = [("mri", build_problem(mri_system(), mri_workload()))]
    for seed, tasks, nodes in [(1, 9, 3), (2, 17, 5), (3, 33, 7)]:
        system = synthetic_system(nodes, seed=seed)
        wf = random_layered_workflow(tasks, seed=seed, max_cores=8, comm=True)
        out.append((f"synth{seed}", build_problem(system, Workload((wf,)))))
    wf = random_layered_workflow(12, seed=9, max_cores=2, comm=True)
    out.append(("narrow", build_problem(_narrow_system(4), Workload((wf,)))))
    return out


# -----------------------------------------------------------------------------
# rank-select primitive
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("seed,width", [(0, 5), (1, 16), (2, 64), (3, 7)])
def test_rank_select_matches_stable_sort(seed, width):
    rng = np.random.default_rng(seed)
    # heavy ties stress the stable tie-break
    row = rng.choice([0.0, 1.5, 2.0, 7.25, 1e30], size=width).astype(np.float32)
    ranks = np.asarray(stable_ranks(jnp.asarray(row)))
    assert sorted(ranks.tolist()) == list(range(width))  # a permutation
    srow = np.sort(row, kind="stable")
    for c in (1, 2, width // 2 + 1, width):
        kth = np.asarray(kth_from_ranks(jnp.asarray(row), jnp.asarray(ranks), c))
        assert kth == srow[c - 1]
        upd = np.asarray(update_from_ranks(jnp.asarray(row), jnp.asarray(ranks), c, 99.0))
        # multiset semantics: c smallest replaced with the fill value
        expect = np.sort(np.concatenate([srow[c:], np.full(c, 99.0, np.float32)]))
        np.testing.assert_array_equal(np.sort(upd), expect)


# -----------------------------------------------------------------------------
# three-way bit-for-bit equivalence
# -----------------------------------------------------------------------------


@pytest.mark.parametrize("name,problem", _problems())
def test_jnp_pallas_numpy_bit_for_bit(name, problem):
    packed = pack(problem)  # the canonical bucket-padded representation
    jp = packed.device_arrays()
    rng = np.random.default_rng(hash(name) % 2**31)
    pop = 8
    A = rng.integers(0, problem.num_nodes, (pop, problem.num_tasks))
    # padded task columns pin to node 0 (the engine pads internally too)
    A_pad = np.zeros((pop, packed.bucket[0]), np.int64)
    A_pad[:, : problem.num_tasks] = A

    _, mk_jnp = make_fitness_fn(problem)(A)
    mk_jnp = np.asarray(mk_jnp)

    for stream in (False, True):
        mk_k, viol_k = population_makespan_pallas(
            jnp.asarray(A_pad, jnp.int32),
            jp["durations"], jp["cores"], jp["data"], jp["feasible"],
            jp["release"], jp["pred_rows"], jp["dtr"], jp["init_free"],
            tile=4, stream=stream,
        )
        np.testing.assert_array_equal(np.asarray(mk_k), mk_jnp)

    for k in range(pop):
        s32 = evaluate_assignment(problem, A[k], dtype=np.float32)
        assert np.float32(s32.makespan) == mk_jnp[k]
        assert s32.violations == int(np.asarray(viol_k)[k])
        # f64 oracle stays the ground truth within float tolerance
        s64 = evaluate_assignment(problem, A[k])
        assert s64.makespan == pytest.approx(float(mk_jnp[k]), rel=1e-4, abs=1e-4)


# -----------------------------------------------------------------------------
# batched multi-instance API
# -----------------------------------------------------------------------------


def test_bucket_padding_neutral():
    problems = [p for _, p in _problems() if p.num_nodes <= 8]
    rng = np.random.default_rng(7)
    pops = [rng.integers(0, p.num_nodes, (5, p.num_tasks)) for p in problems]
    batched = evaluate_population_batch(problems, pops)
    for (obj_b, mk_b), problem, pop in zip(batched, problems, pops):
        obj_u, mk_u = make_fitness_fn(problem)(pop)
        np.testing.assert_array_equal(mk_b, np.asarray(mk_u))
        np.testing.assert_array_equal(obj_b, np.asarray(obj_u))


def test_one_compile_per_bucket_table9_sizes():
    sizes = [(5, 5), (50, 50), (500, 500)]

    def family(seed_offset):
        probs = []
        for n_nodes, n_tasks in sizes:
            system = synthetic_system(n_nodes, seed=n_nodes + seed_offset)
            workload = synthetic_workload(n_tasks, seed=n_tasks + seed_offset)
            probs.append(build_problem(system, workload))
        return probs

    compiled_at_start = fitness_cache_sizes()[1]
    probs_a = family(0)
    pops_a = [np.random.default_rng(1).integers(0, p.num_nodes, (4, p.num_tasks)) for p in probs_a]
    buckets = {bucket_of(p) for p in probs_a}
    evaluate_population_batch(probs_a, pops_a)
    compiled_after_first = fitness_cache_sizes()[1]
    assert compiled_after_first - compiled_at_start <= len(buckets)

    # fresh candidate populations over instances with the same buckets →
    # pure jit cache hits, zero new XLA compiles
    pops_a2 = [np.random.default_rng(2).integers(0, p.num_nodes, (4, p.num_tasks)) for p in probs_a]
    evaluate_population_batch(probs_a, pops_a2)
    assert fitness_cache_sizes()[1] == compiled_after_first

    # a second scenario family only compiles for buckets it hasn't seen
    probs_b = family(1)
    pops_b = [np.random.default_rng(3).integers(0, p.num_nodes, (4, p.num_tasks)) for p in probs_b]
    new_buckets = {bucket_of(p) for p in probs_b} - buckets
    evaluate_population_batch(probs_b, pops_b)
    assert fitness_cache_sizes()[1] - compiled_after_first <= len(new_buckets)
    # and re-running it is again compile-free
    evaluate_population_batch(probs_b, pops_b)
    assert fitness_cache_sizes()[1] - compiled_after_first <= len(new_buckets)


def test_ga_sweep_valid_schedules():
    from repro.core.metaheuristics import ga_sweep

    problems = []
    for seed, tasks, nodes in [(11, 6, 3), (12, 10, 4)]:
        system = synthetic_system(nodes, seed=seed)
        wf = random_layered_workflow(tasks, seed=seed, max_cores=4, feature_pool=("F1",))
        problems.append(build_problem(system, Workload((wf,))))
    results = ga_sweep(problems, pop_size=16, generations=8, seed=0)
    assert len(results) == len(problems)
    for res, problem in zip(results, problems):
        assert res.schedule.violations == 0
        assert verify_schedule(problem, res.schedule) == []
        assert res.history.shape == (8,)


def test_solve_problems_batched_dispatch():
    from repro.core import solve_problems

    problems = []
    for seed in (21, 22, 23):
        system = synthetic_system(3, seed=seed)
        wf = random_layered_workflow(7, seed=seed, max_cores=4, feature_pool=("F1",))
        problems.append(build_problem(system, Workload((wf,))))
    reports = solve_problems(problems, technique="ga", pop_size=16, generations=6, seed=1)
    assert len(reports) == 3
    for rep, problem in zip(reports, problems):
        assert rep.schedule.technique == "ga"
        assert verify_schedule(problem, rep.schedule) == []


def test_dead_link_blocks_even_zero_data_edges():
    """A dead link (non-finite rate) must block dependent placement even when
    the edge carries zero data — the additive transfer penalty, not the
    multiplicative factor, enforces this."""
    from repro.core.heuristics import heft
    from repro.core.workload_model import Task, Workflow

    nodes = [
        Node(f"n{i}", {"cores": 4, "memory": 1.0}, frozenset({"F1"}),
             {"processing_speed": 1.0, "data_transfer_rate": 10.0})
        for i in range(2)
    ]
    # no inter-node link: off-diagonal +inf is the canonical dead-link
    # encoding (it JSON-round-trips as -1.0; NaN rates are rejected at
    # System construction)
    dead = np.full((2, 2), np.inf)
    system = make_system(nodes, dtr=dead)
    wf = Workflow(
        "W",
        (
            Task("a", cores=1, data=0.0, work=1.0, features=frozenset({"F1"})),
            Task("b", cores=1, data=0.0, work=10.0, features=frozenset({"F1"}), deps=("a",)),
        ),
    )
    problem = build_problem(system, Workload((wf,)))
    assert problem.transfer_penalty is not None
    sched = heft(problem)
    # both tasks must co-locate: crossing the dead link is "infinitely" late
    assert sched.assignment[0] == sched.assignment[1]
    assert sched.makespan < 1e9
    assert verify_schedule(problem, sched) == []


def test_makespan_autotune_envelope():
    from repro.kernels import ops

    from repro.kernels import makespan

    # small instance: transfer times VMEM-resident
    assert ops._makespan_mode(200, 50, 64, 8) is False
    # the paper's largest MH cell (Table IX, 500x500) runs resident
    assert ops._makespan_mode(500, 500, 64, 39) is False
    # [T, MAXP] transfer times alone bust the budget → DMA-streamed mode
    assert ops._makespan_mode(2000, 400, 64, 48) is True
    # N·CMAX core state alone busts the budget → jnp fallback
    assert ops._makespan_mode(12, 3000, 512, 4) is None
    # predecessor ids beyond SMEM → jnp fallback
    assert ops._makespan_mode(5000, 10, 8, 200) is None
    # streaming never costs VMEM, and the budget leaves Mosaic headroom on v5e
    for shape in [(200, 50, 64, 8), (500, 500, 64, 39), (2000, 400, 64, 48)]:
        res = makespan.vmem_bytes(*shape, makespan.LANES, False)
        assert makespan.vmem_bytes(*shape, makespan.LANES, True) <= res
    assert makespan.VMEM_BUDGET < 128 << 20


def test_weighted_usage_mode_batched():
    w = ObjectiveWeights(alpha=0.5, beta=2.0, usage_mode="weighted")
    problems = [p for _, p in _problems()[:2]]
    rng = np.random.default_rng(3)
    pops = [rng.integers(0, p.num_nodes, (3, p.num_tasks)) for p in problems]
    batched = evaluate_population_batch(problems, pops, w)
    for (obj_b, mk_b), problem, pop in zip(batched, problems, pops):
        obj_u, mk_u = make_fitness_fn(problem, w)(pop)
        np.testing.assert_allclose(obj_b, np.asarray(obj_u), rtol=1e-6)
        np.testing.assert_array_equal(mk_b, np.asarray(mk_u))
