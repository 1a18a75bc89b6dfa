"""Smoke check: the scheduler's device path runs on a TPU, end to end.

    python chip_smoke.py               # one chip: all four phases
    python chip_smoke.py --chips 4     # four chips: instance striping only

One process drives the chip; everything is generated from ``--seed``.  The
phases go through the entry points a user calls:

1. device — the platform must be ``tpu`` (no CPU fallback);
2. the paper's Table IX 500x500 cell — GA on the ``jax`` and ``pallas``
   engines (the Pallas kernel compiled natively), SA/PSO/ACO on ``jax``,
   every schedule verified, and one random population scored bit for bit
   alike by the ``jax``, ``pallas`` and numpy ``oracle`` engines and by the
   kernel's DMA-streamed mode;
3. the scheduling service on the 1008-node ``large`` topology — every
   submission completes, admission batches, nothing falls back, and a replay
   gives identical makespans;
4. digital-twin calibration on the ``small`` topology, against the
   closed-form fit.

``--chips 4`` runs only what exists across chips: an 8-instance 500x500
``ga_sweep`` striped over four chips against the same family on one device,
and a served trace whose admission groups stripe, against its replay on one
device — both bit for bit.

Each phase prints its cold and warm wall time, its compile requests and
persistent-cache hits, and the device's peak bytes in use; these are set-up
facts, not measurements.  Any failed check exits non-zero before the last
line, which is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
REQUIRED_PLATFORM = "tpu"

#: the full-size configuration (the paper's largest MH cell, a 1008-node
#: continuum, the largest calibration preset of the topology campaign:
#: calibrate's L2 term is not scaled per node, so it shrinks every fitted
#: factor by 1/(1 + 0.002 N) and above 64 nodes misses the 5% bound on any
#: platform)
SIZES = {
    "table9": 500,  # synthetic_system / synthetic_workload size and seed
    "pop": 64,
    "generations": 20,
    "sa_steps": 50,
    "mh_iterations": 20,
    "submissions": 64,
    "topology": "large",
    "calibrate": "small",
    "sweep_instances": 8,
}
#: the trace families a generated tiered topology can serve: ``mri``
#: workflows carry durations for the MRI system's own nodes and ``tpu`` ones
#: need feature F9, so neither is feasible on it
SERVICE_FAMILIES = ("stgs", "random")


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    check(info["platform"] == REQUIRED_PLATFORM,
          f"JAX found no TPU (platform {info['platform']!r}); this check runs only on the chip")
    check(info["count"] >= chips, f"--chips {chips} needs {chips} devices, found {info['count']}")
    print(f"device: {json.dumps(info)}", flush=True)
    return info


class Compiles:
    """Counts XLA compile requests and persistent-cache hits via jax.monitoring."""

    def __init__(self) -> None:
        import jax

        self.requests = 0
        self.cache_hits = 0

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.requests, self.cache_hits


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, body, compiles: Compiles, runs=("cold", "warm")) -> list:
    """Run ``body(run_index)`` once per label in ``runs``; print the set-up
    facts."""
    out, times = [], []
    before = compiles.snapshot()
    for run in range(len(runs)):
        t0 = time.perf_counter()
        out.append(body(run))
        times.append(time.perf_counter() - t0)
    after = compiles.snapshot()
    walls = " ".join(f"{label}_s={t!r}" for label, t in zip(runs, times))
    print(
        f"phase {name}: {walls} "
        f"compile_requests={after[0] - before[0]} "
        f"persistent_cache_hits={after[1] - before[1]} "
        f"peak_bytes_in_use={peak_bytes()}",
        flush=True,
    )
    return out


def counter_delta(before: dict, name: str) -> float:
    from repro import obs

    now = obs.METRICS.snapshot()["counters"]
    return now.get(name, 0) - before.get(name, 0)


def table9_problem(size: int, seed_offset: int = 0):
    from repro.core import build_problem, synthetic_system, synthetic_workload

    seed = size + seed_offset
    return build_problem(synthetic_system(size, seed=seed), synthetic_workload(size, seed=seed))


def phase_table9(seed: int, sizes: dict, compiles: Compiles) -> None:
    import numpy as np

    from repro import obs
    from repro.core import verify_schedule
    from repro.core.api import route_problem
    from repro.engine import pack, population_fitness_fn
    from repro.kernels.makespan import population_makespan_pallas

    problem = table9_problem(sizes["table9"])
    ga = {"pop_size": sizes["pop"], "generations": sizes["generations"], "seed": seed}
    solves = [
        ("ga", "jax", ga),
        ("ga", "pallas", ga),
        ("sa", "jax", {"chains": sizes["pop"], "steps": sizes["sa_steps"], "seed": seed}),
        ("pso", "jax", {"pop_size": sizes["pop"], "iterations": sizes["mh_iterations"], "seed": seed}),
        ("aco", "jax", {"ants": sizes["pop"], "iterations": sizes["mh_iterations"], "seed": seed}),
    ]

    def body(run: int) -> dict:
        makespans = {}
        for technique, engine, opts in solves:
            before = obs.METRICS.snapshot()["counters"]
            rep = route_problem(problem, technique=technique, options=opts, engine=engine)
            label = f"{technique}/{engine}"
            problems = verify_schedule(problem, rep.schedule)
            check(problems == [], f"{label} schedule invalid: {problems[:3]}")
            check(rep.fallbacks == (), f"{label} fell back: {rep.fallbacks}")
            if engine == "pallas":  # each solve traces, so counts, its dispatch
                check(counter_delta(before, "engine.traced.ref") == 0,
                      f"{label} evaluated through the jnp reference, not the kernel")
                check(counter_delta(before, "engine.traced.pallas") >= 1,
                      f"{label} never dispatched the Pallas kernel")
            makespans[label] = float(rep.schedule.makespan)
        # the kernel and the jnp core trace the same GA, so the same schedule
        check(makespans["ga/jax"] == makespans["ga/pallas"],
              f"GA differs between engines: {makespans}")

        rng = np.random.default_rng(seed)
        pop = rng.integers(0, problem.num_nodes, (sizes["pop"], problem.num_tasks))
        mk = {
            engine: np.asarray(population_fitness_fn(problem, engine=engine)(pop)[1])
            for engine in ("oracle", "jax", "pallas")
        }
        # the engine picks the VMEM-resident kernel at this size; the
        # DMA-streamed mode scores the same population straight off the kernel
        a = pack(problem, pad=False).device_arrays()
        mk["pallas-streamed"] = np.asarray(population_makespan_pallas(
            pop.astype(np.int32), a["durations"], a["cores"], a["data"], a["feasible"],
            a["release"], a["pred_rows"], a["dtr"], a["init_free"], stream=True,
        )[0])
        for engine in ("jax", "pallas", "pallas-streamed"):
            diff = int(np.sum(mk[engine] != mk["oracle"]))
            check(diff == 0, f"{engine} fitness differs from the f32 oracle on {diff} candidates")
        return makespans

    cold, warm = run_phase("table9_500x500", body, compiles)
    check(cold == warm, f"Table IX makespans moved between runs: {cold} vs {warm}")
    print(f"table9 makespans: {json.dumps(cold)}", flush=True)


def serve_checked(trace, label: str):
    from repro.service import serve_trace

    result = serve_trace(trace)
    bad = [(r.id, r.status, r.reason) for r in result.records if r.status != "completed"]
    check(not bad, f"{label}: {len(bad)} submissions not completed, e.g. {bad[:3]}")
    fell = [(r.id, r.fallbacks) for r in result.records if r.fallbacks]
    check(not fell, f"{label}: fallbacks {fell[:3]}")
    check(result.batch_errors == [], f"{label}: batched solves raised {result.batch_errors[:3]}")
    check(result.batched_groups > 0, f"{label}: admission batched nothing")
    return result


def phase_service(seed: int, sizes: dict, compiles: Compiles) -> None:
    from repro.service import generate_trace

    trace = generate_trace(
        sizes["submissions"], seed=seed, topology=sizes["topology"],
        families=SERVICE_FAMILIES,
    )
    first, replay = run_phase(
        f"service_{sizes['topology']}",
        lambda run: serve_checked(trace, "service" if run == 0 else "service replay"),
        compiles,
    )
    check(first.makespans() == replay.makespans(), "replay changed the makespans")
    s = first.summary()
    print(
        f"service: nodes={trace.system.num_nodes} submissions={s['submissions']} "
        f"completed={s['completed']} solver_calls={s['solver_calls']} "
        f"batched_groups={s['batched_groups']} "
        f"batched_submissions={s['batched_submissions']}",
        flush=True,
    )


def phase_calibration(seed: int, sizes: dict, compiles: Compiles) -> None:
    from repro.core import Workload, random_layered_workflow
    from repro.topology import cached_system, calibration_report, resolve_spec

    system = cached_system(resolve_spec(sizes["calibrate"]))
    workload = Workload(
        (random_layered_workflow(48, name="W48", seed=48, max_cores=4, feature_pool=("F1",)),)
    )
    cold, warm = run_phase(
        f"calibrate_{sizes['calibrate']}",
        lambda run: calibration_report(system, workload, perturb_seed=seed + 7),
        compiles,
    )
    check(cold == warm, "calibration moved between runs")
    # the device's Adam fit converges to the host's closed-form minimizer
    check(abs(cold["speed_factor_rel_mae"] - cold["baseline_rel_mae"]) < 1e-5,
          f"device fit {cold['speed_factor_rel_mae']} vs closed form {cold['baseline_rel_mae']}")
    check(cold["speed_factor_rel_mae"] < 0.05,
          f"speed_factor_rel_mae {cold['speed_factor_rel_mae']} >= 0.05")
    check(cold["twin_error_after"] < cold["twin_error_before"],
          f"twin error did not drop: {cold['twin_error_before']} -> {cold['twin_error_after']}")
    print(
        f"calibration: nodes={cold['nodes']} speed_factor_rel_mae={cold['speed_factor_rel_mae']!r} "
        f"closed_form_rel_mae={cold['baseline_rel_mae']!r} "
        f"twin_error_before={cold['twin_error_before']!r} "
        f"twin_error_after={cold['twin_error_after']!r}",
        flush=True,
    )


def phase_striping(seed: int, sizes: dict, compiles: Compiles, chips: int) -> None:
    import numpy as np

    from repro import obs
    from repro.core.metaheuristics import ga_sweep
    from repro.service import generate_trace

    family = [table9_problem(sizes["table9"], k) for k in range(sizes["sweep_instances"])]
    opts = {"pop_size": sizes["pop"], "generations": sizes["generations"], "seed": seed}

    # striped and one-device runs alternate, so each side has a cold run
    # (its own compile) and a warm one
    runs = (f"{chips}chips_cold", "1chip_cold", f"{chips}chips_warm", "1chip_warm")

    def sweep(run: int):
        shards = chips if run % 2 == 0 else 1
        results = ga_sweep(family, shard=shards, **opts)
        got = obs.METRICS.snapshot()["gauges"].get("mh.ga_sweep.shards")
        check(got == shards, f"ga_sweep ran on {got} shards, asked for {shards}")
        return results

    sweeps = run_phase(f"ga_sweep_{chips}_chips_vs_1", sweep, compiles, runs)
    for run, results in enumerate(sweeps[1:], 1):
        for k, (a, b) in enumerate(zip(sweeps[0], results)):
            check(np.array_equal(a.schedule.assignment, b.schedule.assignment)
                  and np.array_equal(a.history, b.history)
                  and a.schedule.makespan == b.schedule.makespan,
                  f"instance {k}: {runs[run]} sweep differs from {runs[0]}")
    print(f"ga_sweep: {len(family)} instances bit-identical on {chips} chips and on 1", flush=True)

    trace = generate_trace(
        sizes["submissions"], seed=seed, topology=sizes["topology"],
        families=SERVICE_FAMILIES,
    )

    def serve(run: int):
        if run % 2:
            os.environ["REPRO_SHARD_DEVICES"] = "1"  # the one-device replay
        try:
            return serve_checked(trace, f"service {runs[run]}")
        finally:
            os.environ.pop("REPRO_SHARD_DEVICES", None)

    served = run_phase(f"service_{chips}_chips_vs_1", serve, compiles, runs)
    for run, result in enumerate(served):
        if run % 2:
            check(result.sharded_groups == 0, f"the one-device replay striped ({runs[run]})")
        else:
            check(result.sharded_groups > 0, f"no admission group striped across chips ({runs[run]})")
        check(result.makespans() == served[0].makespans(),
              f"service makespans of {runs[run]} differ from {runs[0]}")
    print(
        f"service: sharded_groups={served[0].sharded_groups} "
        f"batched_groups={served[0].batched_groups}, makespans bit-identical on 1 device",
        flush=True,
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the instance-striping path across four chips")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)
    try:
        info = device_info(args.chips)
    except CheckFailed as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    compiles = Compiles()
    try:
        if args.chips == 1:
            phase_table9(args.seed, SIZES, compiles)
            phase_service(args.seed, SIZES, compiles)
            phase_calibration(args.seed, SIZES, compiles)
        else:
            phase_striping(args.seed, SIZES, compiles, args.chips)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
