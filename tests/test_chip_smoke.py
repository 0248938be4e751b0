"""``chip_smoke.py`` and the compile-cache rule, rehearsed on the CPU.

The script itself refuses to run without a TPU; its phases are driven
here at a small size with the Pallas kernels in interpret mode.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax

import chip_smoke
from repro import compile_cache
from repro.data.datasets import generate_records

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_chip_smoke_refuses_cpu():
    """Run as the script itself: JAX sees only CPU devices here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, env=env, timeout=300)
    assert res.returncode == 1
    assert res.stdout == ""                   # no result line
    assert "no TPU" in res.stderr


def test_chip_smoke_main_path_interpret():
    records = generate_records("ycsb", 2048, seed=3)
    out = chip_smoke.run(records, client_backend="pallas_interpret",
                         scan_backend="pallas_interpret", chunk=1024,
                         check_every=1)
    assert len(out["counts"]) == chip_smoke.PANEL
    assert out["rows_on_device"] > 0
    assert out["plane"]["uploads"] > 0


def test_chip_smoke_sharded_spmd_interpret_subprocess():
    """The ``--chips 4`` phase on four host devices: planes on their own
    devices, one SPMD launch, counts equal the sequential launches."""
    code = textwrap.dedent("""
        import json
        import chip_smoke
        from repro.data.datasets import generate_records
        out = chip_smoke.run(generate_records("ycsb", 2048, seed=3),
                             n_shards=4, client_backend="xla",
                             scan_backend="pallas_interpret", chunk=1024)
        print(json.dumps({"spmd": out["plane"]["spmd"],
                          "homes": len({s["device"]
                                        for s in out["plane"]["shards"]}),
                          "on_device": out["rows_on_device"] > 0}))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + ROOT
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    assert json.loads(res.stdout.strip().splitlines()[-1]) == {
        "spmd": True, "homes": 4, "on_device": True}


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was   # untouched
