#!/usr/bin/env python3
"""Schema check for the machine-readable benchmark artifacts.

Validates the JSON documents ``benchmarks.run`` writes
(``BENCH_coexec.json`` / ``BENCH_coexec_multi.json`` /
``BENCH_kernels.json`` / ``BENCH_traffic.json`` /
``BENCH_cluster.json``) so CI fails fast
when a row key is renamed or dropped — downstream perf-trajectory
tooling reads these artifacts across PRs, which makes their shape an
API. Stdlib-only, enforced in CI's docs job and in tier-1 via
tests/test_docs.py.

Checks per document:

* top level: ``schema_version`` (== 2), ``suite`` (a known suite key),
  ``spec`` (a mapping — the resolved CoexecSpec), ``rows`` (non-empty
  list);
* every row carries the full required key set for its suite (see
  ``REQUIRED``), plus its ``kind``'s (``REQUIRED_BY_KIND``), with
  numeric values where numbers are expected.

    python scripts/check_bench_schema.py BENCH_coexec.json \\
        BENCH_coexec_multi.json BENCH_kernels.json BENCH_traffic.json \\
        BENCH_cluster.json
"""
from __future__ import annotations

import json
import numbers
import sys

SCHEMA_VERSION = 2

# required keys per row, by suite; all must be present in every row
REQUIRED: dict[str, dict[str, set]] = {
    "coexec": {
        "all": {"kind", "workload", "memory", "policy", "seconds",
                "packages", "dispatches", "h2d_copies", "d2h_copies",
                "pipeline_depth"},
        "numeric": {"seconds", "packages", "dispatches", "h2d_copies",
                    "d2h_copies", "pipeline_depth"},
    },
    "coexec-multi": {
        "all": {"workload", "tenants", "admission", "fuse", "preempt",
                "policy", "p50_ms", "p99_ms", "fairness",
                "fairness_curve_mean", "fairness_curve_min", "packages",
                "fused_batches", "total_ms"},
        "numeric": {"tenants", "p50_ms", "p99_ms", "fairness",
                    "fairness_curve_mean", "fairness_curve_min",
                    "packages", "fused_batches", "total_ms"},
    },
    "kernels": {
        "all": {"kind", "kernel", "impl", "label", "size", "iters",
                "us_per_call"},
        "numeric": {"size", "iters", "us_per_call"},
    },
    "traffic": {
        "all": {"workload", "arrival", "tenants", "load", "admission",
                "preempt", "shed", "slo_ms", "arrivals", "admitted",
                "shed_count", "p50_ms", "p99_ms", "miss_rate",
                "shed_fraction", "packages", "fused_batches", "total_ms"},
        "numeric": {"tenants", "load", "arrivals", "admitted",
                    "shed_count", "p50_ms", "p99_ms", "miss_rate",
                    "shed_fraction", "packages", "fused_batches",
                    "total_ms"},
    },
    "cluster": {
        "all": {"name", "workload", "arrival", "admission", "load",
                "min_units", "max_units", "autoscale", "arrivals",
                "admitted", "shed_count", "completed", "lost",
                "duplicated", "reissued", "kills", "joins", "resizes",
                "p50_ms", "p99_ms"},
        "numeric": {"load", "min_units", "max_units", "arrivals",
                    "admitted", "shed_count", "completed", "lost",
                    "duplicated", "reissued", "kills", "joins",
                    "resizes", "p50_ms", "p99_ms"},
    },
}

# further numeric keys a row of one ``kind`` must carry, by suite: the
# DES rows' modeled idle and host shares (the real engine's rows carry
# none; the profiler's device trace measures those)
REQUIRED_BY_KIND: dict[str, dict[str, set]] = {
    "coexec": {"sim": {"device_idle_frac", "host_overhead_frac"}},
}


def check_doc(path: str, doc) -> list[str]:
    """Validate one artifact document; returns error strings."""
    errors: list[str] = []

    def err(msg: str) -> None:
        errors.append(f"{path}: {msg}")

    if not isinstance(doc, dict):
        return [f"{path}: top level must be a JSON object"]
    if doc.get("schema_version") != SCHEMA_VERSION:
        err(f"schema_version must be {SCHEMA_VERSION}, "
            f"got {doc.get('schema_version')!r}")
    suite = doc.get("suite")
    if suite not in REQUIRED:
        err(f"suite must be one of {sorted(REQUIRED)}, got {suite!r}")
        return errors
    if not isinstance(doc.get("spec"), dict):
        err("spec must be the resolved CoexecSpec mapping")
    rows = doc.get("rows")
    if not isinstance(rows, list) or not rows:
        err("rows must be a non-empty list")
        return errors
    want = REQUIRED[suite]
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            err(f"rows[{i}] is not an object")
            continue
        by_kind = REQUIRED_BY_KIND.get(suite, {}).get(row.get("kind"),
                                                      set())
        missing = sorted((want["all"] | by_kind) - set(row))
        if missing:
            err(f"rows[{i}] missing required key(s) {missing}")
        for key in sorted((want["numeric"] | by_kind) & set(row)):
            if not isinstance(row[key], numbers.Number) \
                    or isinstance(row[key], bool):
                err(f"rows[{i}][{key!r}] must be numeric, "
                    f"got {type(row[key]).__name__}")
    return errors


def main(argv: list[str]) -> int:
    """Validate every artifact path given; returns the exit code."""
    paths = argv or ["BENCH_coexec.json", "BENCH_coexec_multi.json",
                     "BENCH_kernels.json", "BENCH_traffic.json",
                     "BENCH_cluster.json"]
    errors: list[str] = []
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"{path}: unreadable ({e})")
            continue
        errors.extend(check_doc(path, doc))
    for e in errors:
        print(f"check_bench_schema: {e}", file=sys.stderr)
    if not errors:
        print(f"check_bench_schema: OK ({len(paths)} artifact(s))")
    return 1 if errors else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
