"""Docs subsystem stays healthy: mermaid/links parse, docstrings hold,
the public-API snapshot matches, and the examples only use the
non-deprecated (CoexecSpec) surface.

Runs the same stdlib-only checkers as CI's docs job, so a broken doc
link, a stripped public docstring or an accidental API-surface break
fails tier-1 locally too.
"""
import ast
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

# the kwarg-era entry points (all emit DeprecationWarning); examples must
# demonstrate the spec surface only — see docs/api.md's deprecation table
DEPRECATED_CALLS = {"make_scheduler", "package_kernel"}
DEPRECATED_METHODS = {"config"}


def _run(script: str, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    return subprocess.run([sys.executable, str(REPO / "scripts" / script),
                           *args],
                          capture_output=True, text=True, timeout=120,
                          env=env)


def test_docs_exist_and_linked_from_readme():
    for page in ("api.md", "architecture.md", "policies.md",
                 "benchmarks.md"):
        assert (REPO / "docs" / page).exists(), f"docs/{page} missing"
    readme = (REPO / "README.md").read_text()
    assert "docs/api.md" in readme
    assert "docs/architecture.md" in readme
    assert "docs/policies.md" in readme
    assert "docs/benchmarks.md" in readme
    # the architecture page links the API page (mermaid + module map)
    assert "api.md" in (REPO / "docs" / "architecture.md").read_text()


def test_check_docs_passes():
    proc = _run("check_docs.py")
    assert proc.returncode == 0, proc.stderr


def test_check_docstrings_passes():
    proc = _run("check_docstrings.py")
    assert proc.returncode == 0, proc.stderr


def test_check_api_snapshot_matches():
    proc = _run("check_api.py")
    assert proc.returncode == 0, (proc.stdout, proc.stderr)


def test_api_snapshot_committed_and_covers_both_modules():
    snap = (REPO / "scripts" / "api_snapshot.txt").read_text()
    assert "repro.api.CoexecSpec" in snap
    assert "repro.core.CoexecutorRuntime" in snap


def _deprecated_uses(path: pathlib.Path) -> list[str]:
    """Calls to deprecated surface in one source file (by AST)."""
    tree = ast.parse(path.read_text())
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in DEPRECATED_CALLS:
            hits.append(f"{path.name}:{node.lineno} {fn.id}()")
        elif isinstance(fn, ast.Attribute) and fn.attr in (
                DEPRECATED_CALLS | DEPRECATED_METHODS):
            # `.config(...)` on anything is the runtime's legacy surface;
            # argparse etc. don't define a .config() so this stays exact
            hits.append(f"{path.name}:{node.lineno} .{fn.attr}()")
    return hits


def test_examples_use_only_non_deprecated_surface():
    hits = []
    for example in sorted((REPO / "examples").glob("*.py")):
        hits += _deprecated_uses(example)
    assert not hits, (
        "examples must demonstrate the CoexecSpec surface, not the "
        f"deprecated kwarg API: {hits}")


def test_bench_schema_checker_accepts_and_rejects():
    """The artifact schema checker passes a well-formed document and
    names the violation for a malformed one (stdlib import, no subprocess
    needed — the same code CI's docs job runs)."""
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import check_bench_schema as cbs
    finally:
        sys.path.pop(0)

    good = {
        "schema_version": cbs.SCHEMA_VERSION, "suite": "coexec-multi",
        "spec": {}, "rows": [{
            "workload": "taylor", "tenants": 8, "admission": "wfq",
            "fuse": False, "preempt": True, "policy": "hguided",
            "p50_ms": 1.0, "p99_ms": 2.0, "fairness": 0.99,
            "fairness_curve_mean": 0.95, "fairness_curve_min": 0.9,
            "packages": 100, "fused_batches": 0, "total_ms": 10.0}]}
    assert cbs.check_doc("good.json", good) == []

    bad = dict(good, schema_version=1)
    assert any("schema_version" in e for e in cbs.check_doc("b.json", bad))
    bad = dict(good, rows=[{k: v for k, v in good["rows"][0].items()
                            if k != "preempt"}])
    assert any("preempt" in e for e in cbs.check_doc("b.json", bad))
    bad = dict(good, rows=[dict(good["rows"][0], p99_ms="fast")])
    assert any("p99_ms" in e for e in cbs.check_doc("b.json", bad))
    bad = dict(good, suite="nope")
    assert any("suite" in e for e in cbs.check_doc("b.json", bad))


def test_examples_import_the_spec_api():
    """The migrated examples actually demonstrate repro.api."""
    for name in ("quickstart.py", "concurrent_requests.py"):
        text = (REPO / "examples" / name).read_text()
        assert "from repro.api import" in text, name
        assert "CoexecSpec" in text, name


def test_coexec_schema_asks_the_idle_columns_of_des_rows_only():
    """The real engine's rows carry no host-clock idle or host shares;
    the DES rows keep theirs, which are model outputs."""
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        import check_bench_schema as cbs
    finally:
        sys.path.pop(0)

    base = {"workload": "taylor", "memory": "usm", "policy": "hguided",
            "seconds": 1.0, "packages": 4, "dispatches": 4,
            "h2d_copies": 0, "d2h_copies": 0, "pipeline_depth": 1}
    sim = dict(base, kind="sim", device_idle_frac=0.1,
               host_overhead_frac=0.2)
    real = dict(base, kind="real")
    doc = {"schema_version": cbs.SCHEMA_VERSION, "suite": "coexec",
           "spec": {}, "rows": [sim, real]}
    assert cbs.check_doc("good.json", doc) == []
    bare_sim = {k: v for k, v in sim.items() if k != "device_idle_frac"}
    errors = cbs.check_doc("b.json", dict(doc, rows=[bare_sim, real]))
    assert any("device_idle_frac" in e for e in errors)


def test_every_span_is_documented_in_architecture():
    from repro.core import tracing

    text = (REPO / "docs" / "architecture.md").read_text()
    missing = [name for name in tracing.SPANS if f"`{name}`" not in text]
    assert tracing.SPANS and not missing, missing
