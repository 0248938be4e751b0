"""Compile the main-path kernels for a TPU v5e at their served widths.

The TPU compiler compiles for a chip that is described, not attached, so
these tests catch what interpret mode cannot — block shapes off the
(8, 128) tiling, casts and gathers Mosaic does not lower, VMEM overuse —
without a chip.  Nothing runs: results are checked by the interpret-mode
and on-chip differential tests.

The topology is described inside a module fixture (never at import): the
TPU library admits one loading process at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.client import encode_chunk
from repro.core.planner import build_plan
from repro.core.workload import generate_workload
from repro.data.datasets import generate_records, predicate_pool
from repro.kernels.plan import compile_plan

CHUNK = 4096          # records per client chunk
N_ROWS = 1 << 20      # device plane rows
N_KEYS = 16           # YCSB's 15 keys + the reserved all-absent row
T, C, Q, S1, TS, LUT = 16, 8, 8, 256, 4, 4096   # 8-query batch buckets


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip cannot read back a persistent-cache entry
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _kernel_named(text: str, name: str) -> bool:
    """The compiled module holds the Mosaic kernel under ``name``: the
    device trace names the kernel's operation so (``name=`` of its
    ``pallas_call``)."""
    return any(line.lstrip().startswith(f"%{name}") and
               "tpu_custom_call" in line for line in text.splitlines())


def _scan_args(spec, n: int = 1):
    """Argument shapes of ``_scan_core_pallas`` for ``n`` shards."""
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    return (
        spec((n * N_KEYS, N_ROWS), u8), spec((n * N_KEYS, N_ROWS), u8),
        spec((n * N_KEYS, N_ROWS), u8), spec((n * N_KEYS, N_ROWS), u8),
        spec((n * N_KEYS, N_ROWS), i32), spec((n * N_KEYS, N_ROWS), i32),
        spec((n * N_ROWS,), i32), spec((n * N_ROWS,), jnp.uint32),
        spec((n * T, N_KEYS), f32), spec((n * T, 1), i32),
        spec((n * T, S1), f32), spec((n * 3, T, S1), f32),
        spec((n * TS,), i32), spec((n * TS, S1), i32),
        spec((n * T, TS), f32), spec((n * LUT,), u8),
        spec((n * T, 1), i32), spec((n * T, 1), i32),
        spec((n * C, T), f32), spec((n * Q, C), f32),
        spec((n * Q, S1), f32), spec((n * Q, S1), f32),
        spec((n * Q, S1), f32),
    )


def test_pushdown_kernel_compiles_for_v5e(one_chip):
    """``clause_bitvectors_fused`` on a 4096-record YCSB chunk with the
    workload-A plan at 1.0 us/record (the served client path)."""
    from repro.kernels.fused import clause_bitvectors_fused

    records = generate_records("ycsb", CHUNK, seed=0)
    workload = generate_workload(
        predicate_pool("ycsb"), n_queries=200, distribution="zipf",
        zipf_a=1.5, rng=np.random.default_rng(0), name="A")
    plan = compile_plan(tuple(
        build_plan(workload, records[:500], budget_us=1.0).plan.clauses))
    R, L = encode_chunk(records).data.shape
    (Pn, Mk), Mv = plan.keys.shape, plan.vals.shape[1]
    Cn = plan.membership.shape[0]
    spec, i32 = _sds(one_chip), jnp.int32
    compiled = clause_bitvectors_fused.lower(
        spec((R, L), jnp.uint8), spec((Pn, Mk), i32), spec((Pn,), i32),
        spec((Pn, Mv), i32), spec((Pn,), i32), spec((Pn,), i32),
        spec((Pn,), i32), spec((Cn, Pn), i32), spec((1,), i32),
        r_blk=256, interpret=False).compile()
    assert _kernel_named(compiled.as_text(), "clause_bitvectors_fused")


def test_scan_kernel_compiles_for_v5e(one_chip):
    """``_scan_core_pallas`` over a 2^20-row plane of YCSB keys with an
    8-query batch (the served device-scan path)."""
    from repro.kernels.scan_fused import _scan_core_pallas

    compiled = _scan_core_pallas.lower(
        *_scan_args(_sds(one_chip)), r_blk=512, interpret=False).compile()
    assert _kernel_named(compiled.as_text(), "_scan_core_pallas")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (256 << 20)   # no lane-padded blowup


def test_packed_scan_compiles_for_v5e(one_chip):
    """``_scan_core_pallas_packed``, the served launch: the same plane
    and batch buckets, every parameter table in one packed int32 buffer
    unpacked in the program; the kernel keeps its name."""
    from repro.kernels.scan_fused import (
        _scan_core_pallas_packed, scan_args_layout,
    )

    layout = scan_args_layout(T, N_KEYS, C, Q, S1, TS, LUT)
    off, shape, _ = layout[-1]
    spec = _sds(one_chip)
    compiled = _scan_core_pallas_packed.lower(
        *_scan_args(spec)[:8], spec((off + int(np.prod(shape)),), jnp.int32),
        layout=layout, r_blk=512, interpret=False).compile()
    assert _kernel_named(compiled.as_text(), "_scan_core_pallas")
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (256 << 20)


def test_spmd_scan_compiles_for_v5e_2x2(topo, one_chip):
    """The sharded plane's SPMD launch: one scan kernel per chip of a
    four-chip host, every argument split on its leading axis."""
    from repro.core.device_scan import _spmd_program

    mesh = Mesh(np.asarray(topo.devices[:4]), ("shards",))
    compiled = _spmd_program(mesh, "pallas", 512).lower(
        *_scan_args(_sds(NamedSharding(mesh, P("shards"))), n=4)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-to-all" not in text
