"""Makespan Pallas kernel vs the jnp oracle vs the numpy oracle, over
problem-shape sweeps (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import Workload, build_problem, evaluate_assignment, mri_system, mri_workload, random_layered_workflow, synthetic_system
from repro.engine import pack
from repro.kernels import ops
from repro.kernels.makespan import population_makespan_pallas
from repro.kernels.ref import population_makespan_ref


def _jp_and_prob(num_tasks, num_nodes, seed):
    if num_tasks == 0:
        prob = build_problem(mri_system(), mri_workload())
    else:
        system = synthetic_system(num_nodes, seed=seed)
        wf = random_layered_workflow(num_tasks, seed=seed, max_cores=8)
        prob = build_problem(system, Workload((wf,)))
    return pack(prob, pad=False).device_arrays(), prob


@pytest.mark.parametrize("num_tasks,num_nodes,seed,pop", [
    (0, 3, 0, 8),       # MRI
    (5, 2, 1, 8),
    (12, 4, 2, 16),
    (24, 6, 3, 16),
    (40, 8, 4, 8),
])
def test_kernel_matches_oracles(num_tasks, num_nodes, seed, pop):
    jp, prob = _jp_and_prob(num_tasks, num_nodes, seed)
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.integers(0, prob.num_nodes, (pop, prob.num_tasks)), jnp.int32)
    mk_ref, v_ref = population_makespan_ref(
        A, durations=jp["durations"], cores=jp["cores"], data=jp["data"],
        feasible=jp["feasible"], release=jp["release"],
        pred_matrix=jp["pred_matrix"], dtr=jp["dtr"], init_free=jp["init_free"],
    )
    mk_k, v_k = population_makespan_pallas(
        A, jp["durations"], jp["cores"], jp["data"], jp["feasible"],
        jp["release"], jp["pred_matrix"], jp["dtr"], jp["init_free"], tile=8,
    )
    np.testing.assert_allclose(np.asarray(mk_k), np.asarray(mk_ref), rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_ref))
    # spot-check vs the numpy oracle
    for k in range(0, pop, max(pop // 4, 1)):
        s = evaluate_assignment(prob, np.asarray(A[k]))
        assert float(mk_k[k]) == pytest.approx(s.makespan, rel=1e-3, abs=1e-3)


def test_ops_dispatch_pads_population():
    jp, prob = _jp_and_prob(0, 3, 0)
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.integers(0, prob.num_nodes, (5, prob.num_tasks)), jnp.int32)
    ops.configure(use_pallas=True)
    try:
        mk, v = ops.population_makespan(
            A, durations=jp["durations"], cores=jp["cores"], data=jp["data"],
            feasible=jp["feasible"], release=jp["release"],
            pred_matrix=jp["pred_matrix"], dtr=jp["dtr"], init_free=jp["init_free"],
        )
    finally:
        ops.configure(use_pallas=False)
    assert mk.shape == (5,)
    mk_ref, _ = population_makespan_ref(
        A, durations=jp["durations"], cores=jp["cores"], data=jp["data"],
        feasible=jp["feasible"], release=jp["release"],
        pred_matrix=jp["pred_matrix"], dtr=jp["dtr"], init_free=jp["init_free"],
    )
    np.testing.assert_allclose(np.asarray(mk), np.asarray(mk_ref), rtol=1e-4)


def test_ga_with_pallas_backend_matches_jnp():
    from repro.core.metaheuristics import ga

    prob = build_problem(mri_system(), mri_workload())
    ops.configure(use_pallas=True)
    try:
        r_pl = ga(prob, seed=3, pop_size=16, generations=8, backend="pallas")
    finally:
        ops.configure(use_pallas=False)
    r_jnp = ga(prob, seed=3, pop_size=16, generations=8, backend="jnp")
    # identical RNG + identical fitness → identical trajectories
    np.testing.assert_allclose(r_pl.history, r_jnp.history, rtol=1e-5)
    assert r_pl.schedule.makespan == pytest.approx(r_jnp.schedule.makespan, rel=1e-5)


# -----------------------------------------------------------------------------
# the population-minor evaluator against the numpy oracle and the Pallas
# interpreter, bit for bit, on the cases its predecessor reads must get right
# -----------------------------------------------------------------------------

_EVAL_KEYS = ("durations", "cores", "data", "feasible", "release", "pred_matrix",
              "dtr", "init_free", "node_cores")


def _layered(tasks, nodes, seed, constraints=None):
    system = synthetic_system(nodes, seed=seed)
    wf = random_layered_workflow(tasks, seed=seed, max_cores=4, comm=True)
    return build_problem(system, Workload((wf,)), constraints)


def _case(name):
    """``(problems, populations [P, T], packed problems)``, one of each per
    instance."""
    from repro.core.workload_model import Constraints

    rng = np.random.default_rng(sum(map(ord, name)))
    if name == "maxp_padding":
        # the bucket's MAXP (16) is well above the real in-degree
        prob = _layered(20, 5, 11)
        assert int((prob.pred_matrix >= 0).sum(axis=1).max()) < 16
        packs = [pack(prob, pack(prob).bucket[:3] + (16,), use_cache=False)]
        pops = [rng.integers(0, prob.num_nodes, (8, prob.num_tasks))]
        probs = [prob]
    elif name == "colocated":
        # half the candidates put every task on one node, the rest on two
        prob = _layered(24, 6, 12)
        one = np.repeat(np.arange(8) % prob.num_nodes, prob.num_tasks).reshape(8, -1)
        two = rng.integers(0, 2, (8, prob.num_tasks))
        probs, pops, packs = [prob], [np.concatenate([one, two])], [pack(prob, use_cache=False)]
    elif name == "dead_link":
        prob = _layered(20, 4, 13)
        probs, pops, packs = [prob], [rng.integers(0, 4, (8, prob.num_tasks))], [pack(prob)]
    elif name == "deadlines":
        prob = _layered(24, 5, 14)
        probe = evaluate_assignment(prob, np.zeros(prob.num_tasks, np.int64))
        cons = Constraints(deadline={prob.workflow_names[0]: 0.6 * probe.makespan})
        prob = _layered(24, 5, 14, cons)
        probs, pops, packs = [prob], [rng.integers(0, 5, (8, prob.num_tasks))], [pack(prob)]
    else:  # "batched": three instances of one bucket under vmap
        probs = [_layered(14 + 3 * k, 4 + k, 20 + k) for k in range(3)]
        pops = [rng.integers(0, p.num_nodes, (8, p.num_tasks)) for p in probs]
        bucket = tuple(max(d) for d in zip(*(pack(p).bucket for p in probs)))
        packs = [pack(p, bucket, use_cache=False) for p in probs]
    return probs, pops, packs


def _padded(pop, T):
    out = np.zeros((pop.shape[0], T), np.int32)  # padded tasks pin to node 0
    out[:, : pop.shape[1]] = pop
    return out


@pytest.mark.parametrize("name", ["maxp_padding", "colocated", "dead_link", "deadlines",
                                  "batched"])
def test_population_minor_evaluator_bit_for_bit(name):
    import jax

    probs, pops, packs = _case(name)
    constrained = name == "deadlines"
    arrays = [pk.device_arrays() for pk in packs]
    A = jnp.stack([jnp.asarray(_padded(p, pk.bucket[0])) for p, pk in zip(pops, packs)])
    stacked = {k: jnp.stack([a[k] for a in arrays]) for k in _EVAL_KEYS + ("deadline",)}

    def evaluate(assignments, arr):
        kw = {k: arr[k] for k in _EVAL_KEYS}
        return population_makespan_ref(
            assignments, **kw, deadline=arr["deadline"] if constrained else None)

    mk, viol = jax.jit(jax.vmap(evaluate))(A, stacked)
    mk, viol = np.asarray(mk), np.asarray(viol)
    assert np.isfinite(mk).all()
    for b, (prob, pop, arr) in enumerate(zip(probs, pops, arrays)):
        kw = [arr[k] for k in _EVAL_KEYS[:-1]]
        mk_k, viol_k = population_makespan_pallas(
            A[b], *kw, arr["deadline"] if constrained else None, tile=8)
        np.testing.assert_array_equal(np.asarray(mk_k), mk[b])
        np.testing.assert_array_equal(np.asarray(viol_k), viol[b])
        for k in range(pop.shape[0]):
            s32 = evaluate_assignment(prob, pop[k], dtype=np.float32)
            assert np.float32(s32.makespan) == mk[b, k]
            assert s32.violations == viol[b, k]
    if constrained:  # some candidates finish tasks past the deadline
        _, free = population_makespan_ref(A[0], **{k: arrays[0][k] for k in _EVAL_KEYS})
        assert (viol[0] > np.asarray(free)).any()
    if name == "dead_link":
        # the link table as a System stores it, +inf on the diagonal, plus a
        # dead link between nodes 0 and 1: co-located predecessors read +inf
        # and must add no transfer; a product with a one-hot would read NaN
        raw = np.where(np.isfinite(probs[0].dtr), probs[0].dtr, np.inf).astype(np.float32)
        raw[0, 1] = raw[1, 0] = np.inf
        dtr = np.array(arrays[0]["dtr"])
        dtr[: raw.shape[0], : raw.shape[1]] = raw
        kw = {k: arrays[0][k] for k in _EVAL_KEYS}
        kw["dtr"] = jnp.asarray(dtr)
        mk_inf, viol_inf = population_makespan_ref(A[0], **kw)
        assert np.isfinite(np.asarray(mk_inf)).all()
        mk_k, _ = population_makespan_pallas(A[0], *[kw[k] for k in _EVAL_KEYS[:-1]], tile=8)
        np.testing.assert_array_equal(np.asarray(mk_k), np.asarray(mk_inf))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for p in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("batch", [None, 8], ids=["one", "vmapped8"])
def test_task_step_reads_whole_rows_at_table9_bucket(batch):
    """The scan over tasks at the Table IX bucket (512/512/64/64, 64
    candidates) gathers only whole rows: no single element of the link
    table, durations, core counts or finish times is fetched by a
    candidate's own index inside the step."""
    import jax

    from repro.engine.backends import population_fitness_from_arrays

    T, N, C, M, P = 512, 512, 64, 64, 64
    shapes = {"durations": ((T, N), jnp.float32), "cores": ((T,), jnp.int32),
              "data": ((T,), jnp.float32), "feasible": ((T, N), jnp.bool_),
              "release": ((T,), jnp.float32), "pred_matrix": ((T, M), jnp.int32),
              "dtr": ((N, N), jnp.float32), "init_free": ((N, C), jnp.float32),
              "node_cores": ((N,), jnp.int32), "usage_fixed": ((T,), jnp.float32),
              "usage_weighted": ((T, N), jnp.float32), "deadline": ((T,), jnp.float32),
              "cost": ((T, N), jnp.float32), "wf": ((T,), jnp.int32),
              "wf_budget": ((T,), jnp.float32)}
    lead = () if batch is None else (batch,)
    arrays = {k: jax.ShapeDtypeStruct(lead + s, d) for k, (s, d) in shapes.items()}
    pop = jax.ShapeDtypeStruct(lead + (P, T), jnp.int32)

    def fitness(pop, arrays):
        return population_fitness_from_arrays(pop, arrays, 1.0, 1.0, "fixed")

    if batch is not None:
        fitness = jax.vmap(fitness)
    jaxpr = jax.make_jaxpr(fitness)(pop, arrays).jaxpr
    scans = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [T]  # one scan, over the tasks
    body = scans[0].params["jaxpr"].jaxpr
    gathers = [e for e in _eqns(body) if e.primitive.name == "gather"]
    assert gathers  # the step does read rows: finish times, rates, core state
    for eqn in gathers:
        operand = eqn.invars[0].aval.shape
        sizes = eqn.params["slice_sizes"]
        assert sizes[-1] == operand[-1] > 1, (operand, sizes)
