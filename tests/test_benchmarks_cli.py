"""The benchmark driver CLI contract.

* a typo'd suite name exits nonzero (CI must not pass while measuring
  nothing);
* the ``kernels`` suite produces the schema-tagged ``BENCH_kernels.json``
  artifact with one row per (wrapper, impl) pair — at least two impl
  variants per kernel, validated by ``scripts/check_bench_schema.py``
  (the same checker CI's docs job runs).
"""
import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run_driver(*args: str, cwd=None) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    # benchmarks.run turns on the persistent compile cache; keep test runs
    # from writing one
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return subprocess.run([sys.executable, "-m", "benchmarks.run", *args],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=cwd or REPO)


def test_unknown_suite_exits_nonzero():
    proc = _run_driver("nope")
    assert proc.returncode == 2
    assert "unknown suite" in proc.stderr
    assert "Traceback" not in proc.stderr


def test_unknown_suite_fails_even_next_to_a_known_one():
    """A typo in a suite list still fails the run after the valid suites
    execute (the pre-fix driver printed a warning and exited 0)."""
    proc = _run_driver("nope", "kernels", "--smoke",
                       "--bench-kernels-json", os.devnull)
    assert proc.returncode == 2
    assert "unknown suite nope" in proc.stderr
    # the valid suite still ran and reported its rows first
    assert "kernel/" in proc.stdout


def test_kernels_smoke_rows_cover_impl_axis():
    from benchmarks import kernel_micro

    rows = kernel_micro.structured_rows(smoke=True)
    by_kernel = {}
    for row in rows:
        by_kernel.setdefault(row["kernel"], set()).add(row["impl"])
        assert row["kind"] == "kernel"
        assert row["us_per_call"] > 0
    assert len(by_kernel) == 8                   # every public wrapper
    for name, impls in by_kernel.items():
        assert len(impls) >= 2, (
            f"{name}: need >=2 impl variants per kernel, got {impls}")


def test_kernels_artifact_passes_schema_check(tmp_path):
    from benchmarks import kernel_micro
    from benchmarks.run import write_bench_doc
    from repro.api import CoexecSpec

    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import check_bench_schema as cbs
    finally:
        sys.path.pop(0)

    rows = kernel_micro.structured_rows(smoke=True)
    path = tmp_path / "BENCH_kernels.json"
    write_bench_doc(str(path), "kernels", CoexecSpec(), rows)
    doc = json.loads(path.read_text())
    assert cbs.check_doc(str(path), doc) == []
    assert doc["suite"] == "kernels"
    assert doc["schema_version"] == cbs.SCHEMA_VERSION


def test_real_rows_carry_no_host_clock_idle_columns():
    """The real engine's rows drop the host-clock idle and host shares
    (two units on one device counted its time twice); the DES rows keep
    their modeled ones."""
    from repro.launch.serve import (coexec_real_rows, coexec_sim_rows,
                                    default_serve_spec)

    spec = default_serve_spec()
    spec = spec.replace(workload=spec.workload.replace(
        name="taylor", items=256, requests=2, concurrent=2))
    idle = {"device_idle_frac", "host_overhead_frac"}
    real = coexec_real_rows(spec, policies=("dyn4",))
    assert real and all(not idle & set(r) for r in real)
    sim = coexec_sim_rows(spec, policies=("dyn4",))
    assert sim and all(idle <= set(r) for r in sim)
