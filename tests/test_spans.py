"""The served path's spans and the kernels' program names.

Every stage of the served path opens a ``ciao.*`` span
(``repro.core.telemetry.span``) in the profiler's trace, on the device
trace's clock; the host-path modules never import JAX for it.  The
benchmark's kernel metrics find their programs in the trace by the
``PROGRAM`` substring each reader declares, so the jitted entry points'
names are pinned here.
"""
from __future__ import annotations

import ast
import glob
import importlib.util
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core.predicates import Query
from repro.core.server import CiaoStore, PushdownPlan
from repro.data.datasets import generate_records, predicate_pool

ROOT = Path(__file__).resolve().parents[1]
HOST_PATH = ["core/server.py", "core/client.py", "serve/store_engine.py",
             "core/telemetry.py"]
SPANS = [
    "ciao.serve.query", "ciao.scan.resolve", "ciao.scan.prepare",
    "ciao.plane.sync", "ciao.scan.params", "ciao.scan.launch",
    "ciao.scan.pack", "ciao.scan.upload", "ciao.scan.dispatch",
    "ciao.scan.fetch", "ciao.scan.assemble", "ciao.client.encode",
    "ciao.client.pushdown", "ciao.serve.submit", "ciao.serve.apply",
    "ciao.store.lock_wait", "ciao.store.ingest", "ciao.store.parse",
    "ciao.store.build", "ciao.store.promote", "ciao.serve.refresh",
    "ciao.store.snapshot",
]


@pytest.mark.parametrize("module", HOST_PATH)
def test_host_path_modules_do_not_import_jax(module):
    tree = ast.parse((ROOT / "src/repro" / module).read_text())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    names |= {n.module or "" for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)}
    assert not {m for m in names if m.split(".")[0] == "jax"}


def test_span_without_jax_loaded_is_a_no_op():
    """The telemetry module alone (the package imports JAX elsewhere):
    a span opens and closes without loading JAX."""
    code = ("import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location("
            "'telemetry', sys.argv[1])\n"
            "mod = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(mod)\n"
            "with mod.span('ciao.x'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules\n")
    p = subprocess.run(
        [sys.executable, "-c", code, str(ROOT / "src/repro/core/telemetry.py")],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr


def _host_span_names(trace_dir: str) -> set[str]:
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return {e.name.split("#", 1)[0]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}


def test_served_path_profile_holds_every_span(tmp_path):
    """A tiny served ingest (encode, pushdown kernel, submit, the writer's
    apply) and device queries, one of which promotes raw rows, traced on
    the CPU: every stage's span is in the profile."""
    from repro.core.client import encode_chunk
    from repro.kernels.engine import KernelEngine
    from repro.serve.store_engine import CiaoServeEngine

    pool = predicate_pool("ycsb")
    plan = PushdownPlan(clauses=pool[:1])   # about half the rows stay raw
    records = generate_records("ycsb", 256, seed=5)
    engine = KernelEngine("pallas_interpret", r_blk=128)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve = CiaoServeEngine(CiaoStore(plan, segment_capacity=64),
                                device_backend="pallas_interpret")
        try:
            for i in range(0, len(records), 128):
                ch = encode_chunk(records[i:i + 128])
                serve.ingest_chunk(ch, engine.eval_fused(ch, plan.clauses))
            serve.quiesce()
            pushed = serve.query(Query(clauses=(pool[0],)), mode="device")
            unpushed = serve.query(Query(clauses=(pool[4],)), mode="device")
        finally:
            serve.close()
    finally:
        jax.profiler.stop_trace()
    assert pushed.rows_on_device and unpushed.raw_parsed
    missing = set(SPANS) - _host_span_names(str(tmp_path))
    assert not missing


def _programs() -> dict[str, str]:
    """``PROGRAM`` of each kernel metric reader under ``bench/metrics``."""
    out = {}
    for path in sorted((ROOT / "bench/metrics").glob("*.py")):
        spec = importlib.util.spec_from_file_location(path.stem, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if hasattr(mod, "PROGRAM"):
            out[path.stem] = mod.PROGRAM
    return out


def _lower_scan():
    from repro.kernels.scan_fused import _scan_core_pallas
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8
    K, N, T, C, Q, S1, Ts, L = 8, 256, 8, 8, 8, 128, 1, 64
    s = jax.ShapeDtypeStruct
    return _scan_core_pallas.lower(
        s((K, N), u8), s((K, N), u8), s((K, N), u8), s((K, N), u8),
        s((K, N), i32), s((K, N), i32), s((N,), i32), s((N,), jnp.uint32),
        s((T, K), f32), s((T, 1), i32), s((T, S1), f32),
        s((3, T, S1), f32), s((Ts,), i32), s((Ts, S1), i32),
        s((T, Ts), f32), s((L,), u8), s((T, 1), i32), s((T, 1), i32),
        s((C, T), f32), s((Q, C), f32), s((Q, S1), f32), s((Q, S1), f32),
        s((Q, S1), f32), r_blk=128, interpret=True)


def _lower_scan_packed():
    from repro.kernels.scan_fused import (
        _scan_core_pallas_packed, scan_args_layout,
    )
    i32, u8 = jnp.int32, jnp.uint8
    K, N, T, C, Q, S1, Ts, L = 8, 256, 8, 8, 8, 128, 1, 64
    layout = scan_args_layout(T, K, C, Q, S1, Ts, L)
    off, shape, _ = layout[-1]
    s = jax.ShapeDtypeStruct
    return _scan_core_pallas_packed.lower(
        s((K, N), u8), s((K, N), u8), s((K, N), u8), s((K, N), u8),
        s((K, N), i32), s((K, N), i32), s((N,), i32), s((N,), jnp.uint32),
        s((off + math.prod(shape),), i32),
        layout=layout, r_blk=128, interpret=True)


def _lower_pushdown():
    from repro.kernels.fused import clause_bitvectors_fused
    i32 = jnp.int32
    R, Lr, P, Mk, Mv, C = 256, 128, 2, 8, 8, 1
    s = jax.ShapeDtypeStruct
    return clause_bitvectors_fused.lower(
        s((R, Lr), jnp.uint8), s((P, Mk), i32), s((P,), i32),
        s((P, Mv), i32), s((P,), i32), s((P,), i32), s((P,), i32),
        s((C, P), i32), s((1,), i32), r_blk=128, interpret=True)


def test_program_names_hold_what_the_kernel_metrics_look_for():
    """A rename of either jitted entry point fails here instead of
    leaving the benchmark's kernel metrics silently empty."""
    programs = _programs()
    assert set(programs) == {
        "client_kernel_roofline", "client_kernel_us_per_krow",
        "scan_kernel_roofline", "scan_kernel_us_per_query"}
    names = {"scan": _lower_scan().as_text().splitlines()[0],
             "client": _lower_pushdown().as_text().splitlines()[0]}
    for metric, program in programs.items():
        head = names[metric.split("_", 1)[0]]
        assert head.startswith(f"module @jit_{program}"), (metric, head)


def test_packed_scan_program_name_holds_the_scan_kernel_metrics_program():
    """The served launch runs ``_scan_core_pallas_packed``: its module's
    name still holds what every scan kernel metric looks for."""
    head = _lower_scan_packed().as_text().splitlines()[0]
    scan = {m: p for m, p in _programs().items() if m.startswith("scan_")}
    assert scan
    for metric, program in scan.items():
        assert head.startswith("module @jit_") and program in \
            head.split()[1], (metric, head)
