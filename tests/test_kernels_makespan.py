"""Makespan Pallas kernel vs the jnp oracle vs the numpy oracle, over
problem-shape sweeps (interpret mode on CPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import Workload, build_problem, evaluate_assignment, mri_system, mri_workload, random_layered_workflow, synthetic_system
from repro.engine import pack
from repro.engine.packed import task_rows
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
        pred_rows=jp["pred_rows"], dtr=jp["dtr"], init_free=jp["init_free"],
    )
    mk_k, v_k = population_makespan_pallas(
        A, jp["durations"], jp["cores"], jp["data"], jp["feasible"],
        jp["release"], jp["pred_rows"], jp["dtr"], jp["init_free"], tile=8,
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
            pred_rows=jp["pred_rows"], dtr=jp["dtr"], init_free=jp["init_free"],
        )
    finally:
        ops.configure(use_pallas=False)
    assert mk.shape == (5,)
    mk_ref, _ = population_makespan_ref(
        A, durations=jp["durations"], cores=jp["cores"], data=jp["data"],
        feasible=jp["feasible"], release=jp["release"],
        pred_rows=jp["pred_rows"], dtr=jp["dtr"], init_free=jp["init_free"],
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

_EVAL_KEYS = ("durations", "cores", "data", "feasible", "release", "pred_rows",
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
        tb = pack(prob).bucket[0]
        packs = [pack(prob, pack(prob).bucket[:3] + (16, tb), use_cache=False)]
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
    """Every equation of ``jaxpr`` and of the jaxprs nested in it, short of
    a Pallas kernel's body (its loops and reads are Mosaic's, not XLA's)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
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
              "release": ((T,), jnp.float32), "pred_rows": ((T, M), jnp.int32),
              "row_task": ((T,), jnp.int32), "row_last": ((T,), jnp.bool_),
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


# -----------------------------------------------------------------------------
# predecessor rows: a join spread over several rows scores bit for bit like
# one dense row of all its predecessors
# -----------------------------------------------------------------------------

_TASK_KEYS = tuple(k for k in _EVAL_KEYS if k != "pred_rows")


def _montage(rows, cols, nodes, seed):
    from repro.core import montage_workflow

    system = synthetic_system(nodes, seed=seed)
    return build_problem(system, Workload((montage_workflow(rows, cols, seed=seed),)))


def _dense_makespans(prob, pop):
    """The evaluator with one dense row of every task's predecessors, as the
    engine packed them before predecessor rows: ``[T, MAXP]``, no
    ``row_task``."""
    dense = pack(prob, use_cache=False)
    assert dense.bucket[4] == dense.bucket[0]  # every task fits one row
    arr = dense.device_arrays()
    mk, viol = population_makespan_ref(
        jnp.asarray(_padded(pop, dense.bucket[0])), **{k: arr[k] for k in _TASK_KEYS},
        pred_rows=arr["pred_rows"])
    return np.asarray(mk), np.asarray(viol)


def _row_case(name):
    """``(problems, populations, buckets)``: Montage mosaics whose joins take
    several rows at a forced row width ``K``, alone or mixed under vmap."""
    from repro.engine import common_bucket

    grids = {"3x3": [(3, 3, 6, 31)], "4x5": [(4, 5, 8, 32)],
             "mixed": [(3, 3, 6, 33), (4, 5, 8, 34), (2, 5, 5, 35)]}[name.split("_k")[0]]
    probs = [_montage(*g) for g in grids]
    width = int(name.split("_k")[1])
    t, n, c, _, _ = common_bucket(probs)
    rows = max(int(task_rows(p, width).sum()) + t - p.num_tasks for p in probs)
    rng = np.random.default_rng(sum(map(ord, name)))
    pops = [rng.integers(0, p.num_nodes, (8, p.num_tasks)) for p in probs]
    return probs, pops, (t, n, c, width, rows + 5)  # 5 filler rows


@pytest.mark.parametrize("name", ["3x3_k2", "3x3_k4", "4x5_k2", "4x5_k4", "mixed_k4"])
def test_predecessor_rows_bit_for_bit(name):
    """At a forced row width the joins (in-degree 20 and 9 on a 3x3 mosaic,
    55 and 20 on 4x5) span several rows, and instances under vmap need
    different row counts: every makespan is the dense evaluator's and the
    f32 oracle's bit for bit, and within float tolerance of the f64 oracle;
    the Pallas dispatcher refuses the rows and falls back to the same
    evaluator."""
    import jax

    from repro import obs
    from repro.engine.backends import population_fitness_from_arrays

    probs, pops, bucket = _row_case(name)
    packs = [pack(p, bucket, use_cache=False) for p in probs]
    assert all(int(pk.row_last.sum()) == bucket[0] for pk in packs)
    assert any(int(task_rows(p, bucket[3]).max()) > 1 for p in probs)
    arrays = {k: jnp.stack([pk.device_arrays()[k] for pk in packs])
              for k in packs[0].device_arrays()}
    A = jnp.stack([jnp.asarray(_padded(p, bucket[0])) for p in pops])

    def fitness(pop, arr):
        return population_fitness_from_arrays(pop, arr, 0.0, 1.0, "fixed")

    _, mk = jax.jit(jax.vmap(fitness))(A, arrays)
    mk = np.asarray(mk)
    for b, (prob, pop, pk) in enumerate(zip(probs, pops, packs)):
        dense_mk, dense_viol = _dense_makespans(prob, pop)
        np.testing.assert_array_equal(mk[b], dense_mk)
        for k in range(pop.shape[0]):
            s32 = evaluate_assignment(prob, pop[k], dtype=np.float32)
            assert np.float32(s32.makespan) == mk[b, k]
            s64 = evaluate_assignment(prob, pop[k])
            assert s64.makespan == pytest.approx(float(mk[b, k]), rel=1e-4, abs=1e-4)
        arr = pk.device_arrays()
        before = obs.METRICS.snapshot()["counters"].get("engine.traced.ref", 0)
        mk_p, viol_p = ops.population_makespan(
            A[b], **{k: arr[k] for k in _TASK_KEYS if k != "node_cores"},
            pred_rows=arr["pred_rows"], row_task=arr["row_task"], row_last=arr["row_last"],
            force=True)
        assert obs.METRICS.snapshot()["counters"]["engine.traced.ref"] == before + 1
        np.testing.assert_array_equal(np.asarray(mk_p), dense_mk)
        np.testing.assert_array_equal(np.asarray(viol_p), dense_viol)


def test_one_row_per_task_packs_the_dense_matrix():
    """Where every in-degree fits one row (K = the bucket's MAXP, S = T, as
    every Table IX instance), the rows are the dense ``[T, MAXP]`` matrix
    and the scan is the one-step-per-task program, whatever ``row_task``
    and ``row_last`` hold."""
    import jax

    prob = _layered(60, 12, 36)
    pk = pack(prob, use_cache=False)
    T, _, _, K, S = pk.bucket
    assert S == T and K >= prob.pred_matrix.shape[1] > 1
    dense = np.full((T, K), -1, np.int32)
    dense[: prob.num_tasks, : prob.pred_matrix.shape[1]] = prob.pred_matrix
    np.testing.assert_array_equal(pk.pred_rows, dense)
    np.testing.assert_array_equal(pk.row_task, np.arange(T))
    assert pk.row_last.all()
    arr = pk.device_arrays()
    A = jnp.asarray(_padded(np.zeros((4, prob.num_tasks), np.int64), T))
    kw = {k: arr[k] for k in _TASK_KEYS} | {"pred_rows": arr["pred_rows"]}

    def scans(**rows):
        jaxpr = jax.make_jaxpr(lambda a: population_makespan_ref(a, **kw, **rows))(A).jaxpr
        return [str(e) for e in _eqns(jaxpr) if e.primitive.name == "scan"]

    assert scans() == scans(row_task=arr["row_task"], row_last=arr["row_last"])


# -----------------------------------------------------------------------------
# the core-state row write: the TPU's row-DMA kernel, run in the Pallas TPU
# interpreter, against the scatter every other platform keeps
# -----------------------------------------------------------------------------


def _row_write_case(cmax, lead, seed):
    """``(state, rows, idx)``: a core state of 6 nodes and 8 candidates whose
    rows are stored ``row_width(cmax)`` wide, padding as the evaluator's."""
    from repro.kernels import ref, rowdma

    rng = np.random.default_rng(seed)
    P, N, W = 8, 6, rowdma.row_width(cmax)

    def stored(shape):
        x = np.full(shape + (W,), ref._PAD, np.float32)
        x[..., :cmax] = rng.normal(size=shape + (cmax,))
        return jnp.asarray(x)

    idx = jnp.asarray(rng.integers(0, N, lead + (P,)), jnp.int32)
    return stored(lead + (P, N)), stored(lead + (P,)), idx


def _scattered(state, rows, idx, last):
    import jax

    put = jax.vmap(lambda s, i, r: s.at[i].set(r))(state, idx, rows)
    return put if last else state


@pytest.mark.parametrize("cmax,width", [(64, 128), (256, 256)])
@pytest.mark.parametrize("last", [True, False], ids=["last", "not_last"])
def test_row_dma_writes_the_rows_scatter_writes(cmax, width, last):
    """One instance: the kernel sets row ``idx[p]`` of candidate ``p`` to
    ``rows[p]``, bit for bit as ``.at[i].set``, and a row that is not the
    task's last leaves the state untouched; so does XLA's path, which
    writes the rows as read back."""
    from repro.kernels import rowdma

    assert rowdma.row_width(cmax) == width
    state, rows, idx = _row_write_case(cmax, (), cmax + last)
    want = np.asarray(_scattered(state, rows, idx, last))
    got = rowdma.row_dma(state, rows, idx, jnp.bool_(last), interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    old = state[jnp.arange(idx.shape[0]), idx]
    np.testing.assert_array_equal(
        np.asarray(rowdma.write_rows(state, rows, idx, jnp.bool_(last), old)), want)


@pytest.mark.parametrize("cmax", [64, 256])
@pytest.mark.parametrize("lead", [(3,), (2, 3)], ids=["vmap", "vmap_vmap"])
def test_row_dma_under_vmap_is_one_kernel(cmax, lead):
    """Under vmap (and vmap of vmap) over instances, the ``custom_vmap``
    rule hands every instance's rows to one kernel over ``[B, P, N, W]``,
    and each instance's ``last`` decides for its rows alone."""
    import functools

    import jax

    from repro.kernels import rowdma

    state, rows, idx = _row_write_case(cmax, lead, cmax + len(lead))
    last = jnp.asarray(np.arange(np.prod(lead)).reshape(lead) % 3 != 1)
    write = functools.partial(rowdma.row_dma, interpret=True)
    for _ in lead:
        write = jax.vmap(write)
    got = np.asarray(write(state, rows, idx, last))
    flat = [x.reshape((-1,) + x.shape[len(lead):]) for x in (state, rows, idx)]
    for b, flag in enumerate(np.asarray(last).reshape(-1)):
        want = _scattered(flat[0][b], flat[1][b], flat[2][b], flag)
        np.testing.assert_array_equal(got.reshape(flat[0].shape)[b], np.asarray(want))
    calls = [e for e in _eqns(jax.make_jaxpr(write)(state, rows, idx, last).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].outvars[0].aval.shape == (int(np.prod(lead)),) + state.shape[len(lead):]


@pytest.mark.parametrize("name", ["probe", "batched", "mixed_k4"])
def test_evaluator_scores_equal_with_either_row_write(name, monkeypatch):
    """``population_makespan_ref`` scores every candidate the same, bit for
    bit, whether its rows go back through XLA's scatter (this platform's
    path) or through the TPU's row-DMA kernel: at the compile tests' probe
    bucket, three Table IX-like instances under vmap (``S == T``), and
    Montage mosaics whose joins take several rows (``S > T``; a join's
    earlier rows write nothing)."""
    import functools

    import jax

    from repro import obs
    from repro.kernels import rowdma

    if name == "probe":
        prob = build_problem(
            synthetic_system(3, seed=1),
            Workload((random_layered_workflow(6, seed=1, max_cores=4),)))
        probs, packs = [prob], [pack(prob, (16, 4, 8, 2, 32), use_cache=False)]
        pops = [np.random.default_rng(5).integers(0, 3, (8, prob.num_tasks))]
    elif name == "batched":
        probs, pops, packs = _case(name)
    else:
        probs, pops, bucket = _row_case(name)
        packs = [pack(p, bucket, use_cache=False) for p in probs]
    T, S = packs[0].bucket[0], packs[0].bucket[4]
    assert (S > T) == (name != "batched")
    keys = _TASK_KEYS + ("pred_rows", "row_task", "row_last")
    arrays = {k: jnp.stack([pk.device_arrays()[k] for pk in packs]) for k in keys}
    A = jnp.stack([jnp.asarray(_padded(p, T)) for p in pops])

    def scores():
        return jax.vmap(lambda a, arr: population_makespan_ref(a, **arr))(A, arrays)

    mk, viol = scores()
    before = obs.METRICS.snapshot()["counters"].get("engine.traced.row_dma", 0)
    monkeypatch.setattr(rowdma, "write_rows", functools.partial(rowdma._dma, interpret=True))
    mk_dma, viol_dma = scores()
    assert obs.METRICS.snapshot()["counters"]["engine.traced.row_dma"] == before + 1
    np.testing.assert_array_equal(np.asarray(mk_dma), np.asarray(mk))
    np.testing.assert_array_equal(np.asarray(viol_dma), np.asarray(viol))
    for b, (prob, pop) in enumerate(zip(probs, pops)):
        for k in range(pop.shape[0]):
            assert np.float32(evaluate_assignment(prob, pop[k], dtype=np.float32).makespan) == mk[b, k]


def test_row_write_lowers_to_a_scatter_off_the_tpu():
    """Off the TPU the evaluator's row write lowers to XLA's scatter, the
    program it had before the kernel: no Mosaic call reaches the CPU."""
    import jax

    prob = build_problem(
        synthetic_system(3, seed=1),
        Workload((random_layered_workflow(6, seed=1, max_cores=4),)))
    arr = pack(prob, (16, 4, 8, 2, 32), use_cache=False).device_arrays()
    kw = {k: arr[k] for k in _TASK_KEYS + ("pred_rows", "row_task", "row_last")}
    pop = jnp.asarray(_padded(np.zeros((8, prob.num_tasks), np.int32), 16))
    text = jax.jit(population_makespan_ref).lower(pop, **kw).as_text()
    assert "scatter" in text
    assert "tpu_custom_call" not in text and "row_dma" not in text
