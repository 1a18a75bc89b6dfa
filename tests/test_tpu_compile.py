"""Compile-only checks for a TPU v5e, run without a chip.

The TPU compiler is installed with jaxlib, so the device programs of the
main path can be compiled for a described (not attached) ``v5e:2x2`` here:
what Mosaic or XLA:TPU would refuse on the chip — an unaligned block, more
VMEM than a kernel may use, an op without a TPU lowering — fails in these
tests at no chip time.  Nothing runs, so they say nothing about results or
times.  The topology is described inside a fixture (only the worker that
runs this file loads the TPU library) and every test skips when it cannot be.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import Workload, build_problem, random_layered_workflow, synthetic_system
from repro.engine import pack
from repro.engine.backends import _population_core
from repro.core.metaheuristics import _ga_sweep_core
from repro.kernels import makespan

#: a small problem packed into a bucket with distinct dims, so each array's
#: dims can be told apart and rescaled to the bucket under test
_PROBE_BUCKET = (16, 4, 8, 2, 32)  # (T, N, CMAX, K, S)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fitness_arrays(bucket, one_chip, batch=None):
    """Shape structs of the packed fitness arrays at ``bucket`` (T, N,
    CMAX, K, S), with an optional leading instance axis."""
    problem = build_problem(
        synthetic_system(3, seed=1),
        Workload((random_layered_workflow(6, seed=1, max_cores=4),)),
    )
    probe = pack(problem, _PROBE_BUCKET).numpy_arrays()
    size = dict(zip(_PROBE_BUCKET, bucket))
    lead = () if batch is None else (batch,)
    return {
        k: jax.ShapeDtypeStruct(lead + tuple(size[d] for d in v.shape), v.dtype, sharding=one_chip)
        for k, v in probe.items()
    }


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


#: the custom call of the core-state row write (``repro.kernels.rowdma``)
_ROW_DMA = re.compile(r'= f32\[[\d,]+\]\{[^}]*\} custom-call\(.*custom_call_target="tpu_custom_call".*'
                      r'op_name="([^"]*/row_dma/[^"]*)"')


def _assert_rows_written_by_dma(compiled, state_lead, parent_temp=None):
    """The evaluator writes its core-state rows with the row-DMA kernel,
    counted in the ``fitness`` scope: no scatter into the core state (the
    f32 array whose leading dims are ``state_lead``, at any row width), no
    copy of it, and (where given) no more temporary memory than the program
    that scattered (``parent_temp`` bytes)."""
    text = compiled.as_text()
    op_names = _ROW_DMA.findall(text)
    assert op_names and any("/fitness/" in n for n in op_names), op_names
    assert all(re.search(r"/(vmap\()?fitness\)?/", n) for n in op_names), op_names
    state = "= f32[" + ",".join(map(str, state_lead)) + ","
    for line in text.splitlines():
        if line.lstrip().startswith(("%", "ROOT")) and state in line:
            assert " scatter(" not in line and " copy(" not in line, line
    if parent_temp is not None:
        assert compiled.memory_analysis().temp_size_in_bytes <= parent_temp


@pytest.mark.parametrize("stream", [False, True], ids=["resident", "streamed"])
def test_makespan_kernel_compiles_at_table9_size(one_chip, stream):
    """The Pallas makespan kernel at the paper's 500x500 cell (64-core
    nodes, in-degree up to 39), one 128-candidate tile, through Mosaic."""
    P, T, N, C, M = makespan.LANES, 500, 500, 64, 39

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        sds((P, T), jnp.int32), sds((T, N), jnp.float32), sds((T,), jnp.int32),
        sds((T,), jnp.float32), sds((T, N), jnp.bool_), sds((T,), jnp.float32),
        sds((T, M), jnp.int32), sds((N, N), jnp.float32), sds((N, C), jnp.float32),
        sds((T,), jnp.float32),
    )
    compiled = _compile(
        lambda *a: makespan._population_makespan(
            *a, tile=makespan.LANES, stream=stream, interpret=False
        ),
        *args,
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert makespan.vmem_bytes(T, N, C, M, makespan.LANES, stream) <= makespan.VMEM_BUDGET


def test_jax_population_core_compiles_at_table9_bucket(one_chip):
    """The jax engine's fitness core (the jnp evaluator) at the 500x500
    bucket with a 64-candidate population."""
    arrays = _fitness_arrays((512, 512, 64, 64, 512), one_chip)
    pop = jax.ShapeDtypeStruct((64, 512), jnp.int32, sharding=one_chip)
    core = _population_core("fixed")
    compiled = core.lower(pop, arrays, 1.0, 1.0).compile()
    mem = compiled.memory_analysis()
    assert mem is not None and mem.temp_size_in_bytes < 16 << 30  # fits one v5e
    # 677,376 bytes where the same program scatters its rows; the sorted-row
    # core select's shift stages hold 64,512 bytes more than the rank select
    # did (612,864), with the scatter as with the kernel
    _assert_rows_written_by_dma(compiled, (64, 512), parent_temp=677_376)


def test_ga_sweep_core_compiles_at_service_bucket(one_chip):
    """The batched ``ga_sweep`` program an admission group runs on the
    1008-node ``large`` topology: 4 instances of up to 16 tasks."""
    B, bucket = 4, (16, 1024, 64, 4, 16)
    arrays = _fitness_arrays(bucket, one_chip, batch=B)
    logits = jax.ShapeDtypeStruct((B, bucket[0], bucket[1]), jnp.float32, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=one_chip)
    run = _ga_sweep_core("fixed", 16, 6, 4, 2)
    compiled = run.lower(arrays, logits, keys, 1.0, 1.0, 0.08).compile()
    # XLA:TPU keeps the evaluator's scope in the op_name of its fused ops,
    # which the chip's profiler reports per operation
    assert re.search(r'fusion[.\w]* = .*op_name="[^"]*/fitness/', compiled.as_text())
    assert np.isfinite(compiled.memory_analysis().temp_size_in_bytes)


def test_ga_sweep_core_compiles_at_table9_bucket(one_chip):
    """The batched ``ga_sweep`` program of the Table IX benchmark cell: 8
    instances at the 500x500 bucket, 64 candidates, 20 generations, the
    evaluator's task step reading whole rows population-minor."""
    B, bucket = 8, (512, 512, 64, 64, 512)
    arrays = _fitness_arrays(bucket, one_chip, batch=B)
    logits = jax.ShapeDtypeStruct((B, bucket[0], bucket[1]), jnp.float32, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=one_chip)
    run = _ga_sweep_core("fixed", 64, 20, 4, 2)
    compiled = run.lower(arrays, logits, keys, 1.0, 1.0, 0.08).compile()
    assert re.search(r'fusion[.\w]* = .*op_name="[^"]*/fitness/', compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30  # fits one v5e
    # 154,516,480 bytes where the rows were scattered (the parent program);
    # rows stored 128 lanes wide pack its heap 64,512 bytes (0.04%) looser,
    # and the sorted-row core select's shift stages 129,024 bytes more, with
    # the scatter as with the kernel
    _assert_rows_written_by_dma(
        compiled, (B, 64, 512), parent_temp=154_516_480 + 64_512 + 129_024)


def test_ga_sweep_core_compiles_at_montage_bucket(one_chip):
    """The batched ``ga_sweep`` program of the Montage benchmark cell: 8
    mosaics of 1,019 tasks at the 1024/512/64 bucket, predecessors in 1088
    rows of 16 (the joins of 649, 182 and 183 take 41, 12 and 12 rows), 64
    candidates, 20 generations."""
    B, bucket = 8, (1024, 512, 64, 16, 1088)
    arrays = _fitness_arrays(bucket, one_chip, batch=B)
    assert arrays["pred_rows"].shape == (B, 1088, 16)
    logits = jax.ShapeDtypeStruct((B, bucket[0], bucket[1]), jnp.float32, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=one_chip)
    run = _ga_sweep_core("fixed", 64, 20, 4, 2)
    compiled = run.lower(arrays, logits, keys, 1.0, 1.0, 0.08).compile()
    assert re.search(r'op_name="[^"]*/fitness/[^"]*/preds/', compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30  # fits one v5e
    # 307,444,736 bytes where the rows were scattered (the parent program)
    _assert_rows_written_by_dma(compiled, (B, 64, 512), parent_temp=307_444_736)


def test_sharded_ga_sweep_core_compiles_on_four_chips(topo, monkeypatch):
    """The Table IX sweep striped over a ``v5e:2x2`` by ``shard_map``, 2
    instances a chip (the 4-chip cell): each chip's program writes its
    rows with the row-DMA kernel."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.engine import shard

    mesh = Mesh(np.array(topo.devices), (shard.AXIS,))
    monkeypatch.setattr(shard, "instance_mesh", lambda devices: mesh)
    striped = NamedSharding(mesh, PartitionSpec(shard.AXIS))
    B, bucket = 8, (512, 512, 64, 64, 512)
    arrays = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=striped)
              for k, v in _fitness_arrays(bucket, None, batch=B).items()}
    logits = jax.ShapeDtypeStruct((B, bucket[0], bucket[1]), jnp.float32, sharding=striped)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=striped)
    run = _ga_sweep_core.__wrapped__("fixed", 64, 20, 4, 2, shards=len(topo.devices))
    compiled = run.lower(arrays, logits, keys, 1.0, 1.0, 0.08).compile()
    # 69,344,768 bytes a chip where the rows were scattered (the parent
    # program), and 129,024 more for the sorted-row core select's shift
    # stages, with the scatter as with the kernel
    _assert_rows_written_by_dma(
        compiled, (B // len(topo.devices), 64, 512), parent_temp=69_344_768 + 129_024)
