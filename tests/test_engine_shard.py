"""Multi-device sharded evaluation (`repro.engine.shard`) acceptance suite.

The conftest pins the in-process suite to ONE virtual device
(``--xla_force_host_platform_device_count=1``), so the tests split:

* in-process — shard-count math, pad semantics, the 1-device degenerate
  path (``shard="auto"`` must collapse to exactly today's unsharded core),
  the mesh-aware pack LRU bookkeeping, and option plumbing;
* one subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
  — the real equivalence claims: sharded batched fitness bit-identical
  (f32 objectives + makespans) to the single-device vmapped core AND to the
  numpy oracle; the pad edge (B not divisible by the shard count); sharded
  ``ga_sweep`` returning the same schedules/histories as ``shard="off"``;
  per-device pack-cache residency across all 8 devices;
* the span tree of one traced ``ga_sweep`` call, on one device in-process
  and on four virtual devices in a subprocess.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import ObjectiveWeights, Workload, build_problem, synthetic_system
from repro.core.workload_model import random_layered_workflow
from repro.engine import (
    ENGINES,
    choose_shards,
    local_device_count,
    pack_cache,
    sharded_batched_fitness,
    stack_packed,
    stack_packed_sharded,
)
from repro.engine.shard import pad_batch

REPO = Path(__file__).resolve().parent.parent


def _family(n, tasks=10, nodes=3, seed0=100):
    system = synthetic_system(nodes, seed=nodes)
    return [
        build_problem(
            system,
            Workload((random_layered_workflow(
                tasks, seed=seed0 + i, max_cores=4, feature_pool=("F1",)
            ),)),
        )
        for i in range(n)
    ]


def _mosaics(n, nodes=3):
    """Montage mosaics of 5x6 and 6x6 images: joins of 89 to 110 fits take
    several predecessor rows, and the instances need different row counts."""
    from repro.core import montage_workflow

    system = synthetic_system(nodes, seed=nodes)
    return [build_problem(system, Workload((montage_workflow(5 + i % 2, 6, seed=i),)))
            for i in range(n)]


# -----------------------------------------------------------------------------
# shard-count / padding math (device-count passed explicitly — no jax needed)
# -----------------------------------------------------------------------------


def test_choose_shards_prefers_divisors():
    assert choose_shards(8, 8) == 8
    assert choose_shards(12, 8) == 6  # largest divisor <= fleet, zero pad
    assert choose_shards(16, 8) == 8
    assert choose_shards(9, 8) == 3


def test_choose_shards_small_batches_spread_one_per_device():
    assert choose_shards(6, 8) == 6
    assert choose_shards(2, 8) == 2


def test_choose_shards_degenerate_cases():
    assert choose_shards(0, 8) == 1
    assert choose_shards(1, 8) == 1
    assert choose_shards(64, 1) == 1


def test_choose_shards_falls_back_to_padding():
    # no divisor of 5 in 2..2 — stripe over all 2 devices, pad 5 -> 6
    assert choose_shards(5, 2) == 2
    assert choose_shards(7, 4) == 4  # pad 7 -> 8


def test_pad_batch():
    assert pad_batch(5, 2) == 6
    assert pad_batch(7, 4) == 8
    assert pad_batch(8, 8) == 8
    assert pad_batch(3, 1) == 3


# -----------------------------------------------------------------------------
# 1-device degeneration (the suite's pinned environment)
# -----------------------------------------------------------------------------


def test_auto_shard_on_single_device_is_unsharded_path():
    assert local_device_count() == 1  # conftest pins the suite to 1 device
    problems = _family(4)
    auto = ENGINES.get("jax").batched_fitness(problems)  # shard="auto"
    base = ENGINES.get("jax").batched_fitness(problems, shard=None)
    assert auto.shards == 1 and base.shards == 1
    rng = np.random.default_rng(0)
    Tb = auto.bucket[0]
    A = np.zeros((4, 6, Tb), np.int32)
    A[:, :, :10] = rng.integers(0, problems[0].num_nodes, (4, 6, 10))
    for got, want in zip(auto(A), base(A)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_sharded_stack_single_device_matches_stack_packed():
    problems = _family(3)
    stack = stack_packed_sharded(problems, use_cache=False)
    assert stack.shards == 1
    assert stack.instances == 3 and stack.padded == 3
    arrays, bucket = stack_packed(problems)
    assert stack.bucket == bucket
    for k, v in arrays.items():
        np.testing.assert_array_equal(
            np.asarray(stack.arrays[k]), np.asarray(v)
        )


def test_sharded_fitness_rejects_wrong_instance_count():
    problems = _family(3)
    fitness = sharded_batched_fitness(problems, shards=1)
    A = np.zeros((2, 4, fitness.bucket[0]), np.int32)
    with pytest.raises(ValueError, match="instance rows"):
        fitness(A)


def test_pack_cache_is_mesh_aware():
    problems = _family(3, seed0=700)
    cache = pack_cache()
    stack_packed_sharded(problems)
    first = {d: dict(s) for d, s in cache.device_stats.items()}
    assert first, "device_stats must populate on a sharded stack build"
    assert all(s["resident_bytes"] > 0 for s in first.values())
    again = stack_packed_sharded(problems)
    assert again.shards == 1
    assert any(
        cache.device_stats[d]["hits"] > first[d]["hits"] for d in first
    ), "second stack of the same family must hit the LRU's device buffers"
    # eviction/clear releases the per-device resident bytes
    cache.clear()
    assert all(
        s["resident_bytes"] == 0 for s in cache.device_stats.values()
    )


def test_pack_cache_collector_reports_device_stats():
    from repro.engine.packed import _pack_cache_collector

    stack_packed_sharded(_family(2, seed0=800))
    snap = _pack_cache_collector()
    assert any(k.startswith("device.") for k in snap)


def test_ga_accepts_and_ignores_shard_option():
    from repro.core.metaheuristics import ga

    problem = _family(1)[0]
    res = ga(problem, pop_size=8, generations=2, seed=0, shard=4)
    assert res.schedule is not None


def test_ga_sweep_shard_off_matches_default_on_one_device():
    from repro.core.metaheuristics import ga_sweep

    problems = _family(2)
    a = ga_sweep(problems, pop_size=8, generations=3, seed=0)
    b = ga_sweep(problems, pop_size=8, generations=3, seed=0, shard="off")
    for ra, rb in zip(a, b):
        np.testing.assert_array_equal(
            ra.schedule.assignment, rb.schedule.assignment
        )
        np.testing.assert_array_equal(ra.history, rb.history)


# -----------------------------------------------------------------------------
# the span tree of one traced ga_sweep call
# -----------------------------------------------------------------------------


def _check_sweep_span_tree(spans, instances: int, shards: int) -> None:
    """``spans``: ``(id, parent, name, wall_t0, wall_dur, args)`` of one
    traced call.  It is one ``mh.ga_sweep`` tree: ``prepare``, ``device``
    and one ``mh.finish`` per instance as its children, in that order, with
    the pack (or shard stack) inside ``prepare``, covering at least 95% of
    the call's wall time."""
    by_id = {s[0]: s for s in spans}
    (root,) = [s for s in spans if s[1] is None]
    assert root[2] == "mh.ga_sweep"
    assert root[5]["instances"] == instances and root[5]["shards"] == shards
    bucket = [int(d) for d in root[5]["bucket"].split("x")]
    assert (root[5]["tasks"], root[5]["rows"]) == (bucket[0], bucket[4])
    children = [s for s in spans if s[1] == root[0]]
    assert [s[2] for s in children] == (
        ["mh.ga_sweep.prepare", "mh.ga_sweep.device"] + ["mh.finish"] * instances)
    prepare = children[0]
    assert prepare[5]["h2d_bytes"] > 0
    inside = {s[2] for s in spans if s[1] is not None and by_id[s[1]][2] == prepare[2]}
    assert inside == ({"engine.shard_stack"} if shards > 1 else {"engine.pack"})
    assert sum(s[4] for s in children) >= 0.95 * root[4]
    for s in children:  # children nest inside the call and do not overlap
        assert root[3] <= s[3] and s[3] + s[4] <= root[3] + root[4] + 1e-9
    for a, b in zip(children, children[1:]):
        assert a[3] + a[4] <= b[3] + 1e-9


def _traced_sweep_spans(problems, **kw):
    from repro import obs
    from repro.core.metaheuristics import ga_sweep

    ga_sweep(problems, pop_size=8, generations=3, seed=0, **kw)  # compile
    obs.enable_tracing()
    try:
        ga_sweep(problems, pop_size=8, generations=3, seed=1, **kw)
    finally:
        obs.disable_tracing()
    return [(s.id, s.parent, s.name, s.wall_t0, s.wall_dur, s.args)
            for s in obs.TRACER.spans]


def test_sweep_logits_on_device_match_the_host_mask():
    """The sweep's sampling logits, made on the device from the stacked
    feasibility, equal the host mask they replace: 0 on feasible nodes, node
    0 for a task with none and for padded tasks, ``_NEG`` elsewhere."""
    from repro.core.metaheuristics import _NEG, _sampling_logits
    from repro.engine import stack_packed

    problems = _family(2, tasks=10) + _family(1, tasks=7, nodes=2, seed0=7)
    problems[1].feasible[3] = False  # a task no node can run
    arrays, (Tb, Nb, *_) = stack_packed(problems)
    want = np.full((3, Tb, Nb), _NEG, np.float32)
    for b, p in enumerate(problems):
        safe = p.feasible.copy()
        safe[~safe.any(axis=1), 0] = True
        want[b, : p.num_tasks, : p.num_nodes][safe] = 0.0
        want[b, p.num_tasks :, 0] = 0.0
    got = np.asarray(_sampling_logits()(arrays["feasible"]))
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want)


def test_ga_sweep_copies_each_instance_once():
    """A family that meets again is stacked on the device, and its logits
    are made there: the traced second call copies only its PRNG keys."""
    spans = _traced_sweep_spans(_family(3))
    (prepare,) = [s for s in spans if s[2] == "mh.ga_sweep.prepare"]
    assert prepare[5]["h2d_bytes"] == 3 * 2 * 4


def test_ga_sweep_span_tree_one_device():
    _check_sweep_span_tree(_traced_sweep_spans(_family(3)), instances=3, shards=1)


def test_ga_sweep_reports_predecessor_rows():
    """The call's span and the ``engine.pred_rows`` gauge give the bucket's
    row count ``S`` beside its tasks ``T``: one row per task for a layered
    family, more where Montage joins span several rows."""
    from repro import obs
    from repro.engine import common_bucket

    for problems in (_family(2), _mosaics(3)):
        spans = _traced_sweep_spans(problems)
        _check_sweep_span_tree(spans, instances=len(problems), shards=1)
        T, _, _, _, S = common_bucket(problems)
        root = next(s for s in spans if s[1] is None)
        assert (root[5]["tasks"], root[5]["rows"]) == (T, S)
        assert obs.METRICS.snapshot()["gauges"]["engine.pred_rows"] == S
    assert S > T


_SPAN_TREE_SCRIPT = textwrap.dedent(
    """
    import json, sys
    sys.path.insert(0, "tests")
    from test_engine_shard import _family, _traced_sweep_spans
    from repro.engine import local_device_count

    assert local_device_count() == 4, local_device_count()
    print("SPANS " + json.dumps(_traced_sweep_spans(_family(8))))
    """
)


def test_ga_sweep_span_tree_four_devices_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_SHARD_DEVICES", None)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, "-c", _SPAN_TREE_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600, cwd=REPO,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    line = next(x for x in proc.stdout.splitlines() if x.startswith("SPANS "))
    _check_sweep_span_tree(json.loads(line[len("SPANS "):]), instances=8, shards=4)


# -----------------------------------------------------------------------------
# 8-virtual-device equivalence (subprocess: conftest pins this process to 1)
# -----------------------------------------------------------------------------

_MULTI_DEVICE_SCRIPT = textwrap.dedent(
    """
    import sys

    import numpy as np

    from repro.core import ObjectiveWeights, Workload, build_problem, synthetic_system
    from repro.core.metaheuristics import ga_sweep
    from repro.core.workload_model import random_layered_workflow
    from repro.engine import ENGINES, choose_shards, local_device_count, pack_cache
    from repro.engine.shard import stack_packed_sharded

    assert local_device_count() == 8, local_device_count()
    assert choose_shards(8) == 8 and choose_shards(12) == 6 and choose_shards(5) == 5

    def family(n, tasks=10, nodes=3, seed0=100):
        system = synthetic_system(nodes, seed=nodes)
        return [
            build_problem(system, Workload((random_layered_workflow(
                tasks, seed=seed0 + i, max_cores=4, feature_pool=("F1",)),)))
            for i in range(n)
        ]

    w = ObjectiveWeights()
    eng = ENGINES.get("jax")
    oracle = ENGINES.get("oracle")
    rng = np.random.default_rng(0)

    # --- B=8 stripes over all 8 devices; bit-identical to the single-device
    # vmapped core AND to the numpy oracle (objectives carry the violation
    # penalty, so matching objectives matches violations too)
    problems = family(8)
    auto = eng.batched_fitness(problems, w)
    assert auto.shards == 8, auto.shards
    base = eng.batched_fitness(problems, w, shard=None)
    Tb = auto.bucket[0]
    A = np.zeros((8, 6, Tb), np.int32)
    A[:, :, :10] = rng.integers(0, problems[0].num_nodes, (8, 6, 10))
    obj_s, mk_s = (np.asarray(x) for x in auto(A))
    obj_1, mk_1 = (np.asarray(x) for x in base(A))
    assert np.array_equal(obj_s, obj_1) and np.array_equal(mk_s, mk_1)
    for i, p in enumerate(problems):
        obj_o, mk_o = oracle.population_fitness(p, w)(A[i, :, :10])
        assert np.array_equal(np.asarray(mk_o, np.float32),
                              mk_s[i].astype(np.float32)), i
        assert np.array_equal(np.asarray(obj_o, np.float32),
                              obj_s[i].astype(np.float32)), i

    # --- pad edge: B=5 forced onto 2 shards pads to 6 rows; the replica
    # rows are sliced off and results still match the unsharded core
    probs5 = family(5, seed0=300)
    f2 = eng.batched_fitness(probs5, w, shard=2)
    assert f2.shards == 2
    b5 = eng.batched_fitness(probs5, w, shard=None)
    A5 = np.zeros((5, 4, Tb), np.int32)
    A5[:, :, :10] = rng.integers(0, probs5[0].num_nodes, (5, 4, 10))
    for got, want in zip(f2(A5), b5(A5)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == (5, 4)
        assert np.array_equal(got, want)

    # --- sharded ga_sweep == shard="off" at the same seed (schedules AND
    # per-generation histories)
    on = ga_sweep(problems, pop_size=8, generations=3, seed=0)
    off = ga_sweep(problems, pop_size=8, generations=3, seed=0, shard="off")
    for ra, rb in zip(on, off):
        assert np.array_equal(ra.schedule.assignment, rb.schedule.assignment)
        assert np.array_equal(ra.history, rb.history)

    # --- Montage joins over several predecessor rows, instances of unequal
    # row counts: the sharded sweep is the one-device sweep bit for bit
    sys.path.insert(0, "tests")
    from test_engine_shard import _mosaics

    mosaics = _mosaics(4)
    on = ga_sweep(mosaics, pop_size=8, generations=2, seed=0)
    off = ga_sweep(mosaics, pop_size=8, generations=2, seed=0, shard="off")
    for ra, rb in zip(on, off):
        assert np.array_equal(ra.schedule.assignment, rb.schedule.assignment)
        assert np.array_equal(ra.history, rb.history)

    # --- mesh-aware pack LRU: the family's device buffers are resident on
    # all 8 devices and a re-stack hits them
    cache = pack_cache()
    stats0 = {d: dict(s) for d, s in cache.device_stats.items()}
    assert len(stats0) == 8, sorted(stats0)
    assert all(s["resident_bytes"] > 0 for s in stats0.values())
    stack = stack_packed_sharded(problems)
    assert stack.shards == 8 and stack.padded == 8
    assert all(cache.device_stats[d]["hits"] > stats0[d]["hits"]
               for d in stats0)

    print("MULTI-DEVICE-OK")
    """
)


def test_multi_device_equivalence_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("REPRO_SHARD_DEVICES", None)
    env["PYTHONPATH"] = str(REPO / "src")
    proc = subprocess.run(
        [sys.executable, "-c", _MULTI_DEVICE_SCRIPT],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
        cwd=REPO,
    )
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "MULTI-DEVICE-OK" in proc.stdout


def test_shard_devices_env_clamp():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["REPRO_SHARD_DEVICES"] = "2"
    env["PYTHONPATH"] = str(REPO / "src")
    script = (
        "from repro.engine import choose_shards, local_device_count\n"
        "assert local_device_count() == 2, local_device_count()\n"
        "assert choose_shards(8) == 2\n"
        "print('CLAMP-OK')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr
    assert "CLAMP-OK" in proc.stdout
