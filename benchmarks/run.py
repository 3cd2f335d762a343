"""Benchmark driver: one section per paper table/figure + framework
benchmarks. Prints ``name,value,derived`` CSV rows.

  python -m benchmarks.run                 # everything
  python -m benchmarks.run fig5 fig7       # selected artifacts
  python -m benchmarks.run coexec --policy work_stealing --n 16384
  python -m benchmarks.run coexec --smoke  # CI-sized data-plane exercise
  python -m benchmarks.run --list          # registered plugins

The co-execution suites (``coexec`` / ``coexec-multi``) take the same
spec-derived flags as ``repro.launch.serve`` — both CLIs generate them
from the ``repro.api.CoexecSpec`` fields, so a new spec field becomes a
new flag in both tools with no edits here (``--preempt`` arrived that
way). When a coexec suite runs, the driver also writes machine-readable
artifacts: ``BENCH_coexec.json`` (path via ``--bench-json``) with
per-workload/policy/memory throughput plus the data plane's dispatch
and staging-copy counters, and ``BENCH_coexec_multi.json`` (path via
``--bench-multi-json``) with the multi-tenant admission sweep —
fairness curves included, so the preemption win is a tracked quantity.
The ``kernels`` suite likewise writes ``BENCH_kernels.json`` (path via
``--bench-kernels-json``) with one row per (wrapper, impl) pair along
the ``pallas``/``xla``/``ref`` implementation axis, and the ``cluster``
suite writes ``BENCH_cluster.json`` (path via ``--bench-cluster-json``)
with the elastic-pool failure/autoscale scenarios and their exact-once
audit columns. All of these documents carry
``schema_version``/``suite`` fields and are validated by
``scripts/check_bench_schema.py`` in CI's docs job.
"""
from __future__ import annotations

import argparse
import json
import sys


def build_parser(suite_names) -> argparse.ArgumentParser:
    """Suites as positionals + the spec-derived co-execution flags.

    Args:
        suite_names: valid suite keys, for the help text.

    Returns:
        The driver's argparse parser.
    """
    from repro.api import add_spec_args

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("suites", nargs="*", metavar="SUITE",
                    help=f"suites to run (default: all); "
                         f"have {sorted(suite_names)}")
    ap.add_argument("--list", action="store_true",
                    help="print registered schedulers, workloads and "
                         "kernels (with their option fields) and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="shrink the coexec suites to CI-smoke sizes")
    ap.add_argument("--bench-json", default="BENCH_coexec.json",
                    metavar="PATH",
                    help="where to write the machine-readable coexec "
                         "results (default: %(default)s)")
    ap.add_argument("--bench-multi-json", default="BENCH_coexec_multi.json",
                    metavar="PATH",
                    help="where to write the machine-readable coexec-multi "
                         "results (default: %(default)s)")
    ap.add_argument("--bench-kernels-json", default="BENCH_kernels.json",
                    metavar="PATH",
                    help="where to write the machine-readable per-impl "
                         "kernel microbenchmark results "
                         "(default: %(default)s)")
    ap.add_argument("--bench-traffic-json", default="BENCH_traffic.json",
                    metavar="PATH",
                    help="where to write the machine-readable open-loop "
                         "SLO traffic results (default: %(default)s)")
    ap.add_argument("--bench-cluster-json", default="BENCH_cluster.json",
                    metavar="PATH",
                    help="where to write the machine-readable elastic "
                         "cluster results (default: %(default)s)")
    add_spec_args(ap)
    return ap


BENCH_SCHEMA_VERSION = 2


def write_bench_doc(path: str, suite: str, spec, rows: list) -> None:
    """Serialize one suite's structured rows as a schema-tagged artifact.

    Args:
        path: output JSON path.
        suite: suite key (``"coexec"`` / ``"coexec-multi"``) — recorded
            in the document so the schema checker knows the row contract.
        spec: the resolved ``CoexecSpec`` the run used.
        rows: the structured measurement dicts.
    """
    doc = {"schema_version": BENCH_SCHEMA_VERSION, "suite": suite,
           "spec": spec.to_dict(), "rows": rows}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
    print(f"# wrote {path} ({len(rows)} rows)", file=sys.stderr)


def main() -> None:
    from repro.api import registry_listing, spec_from_args

    from . import (cluster_bench, hetero_bench, kernel_micro, paper_figs,
                   roofline_table, traffic_bench)
    from repro.launch.device import device_line, use_compile_cache
    from repro.launch.serve import default_serve_spec

    ap = build_parser(
        list(dict(paper_figs.ALL))
        + ["kernels", "hetero", "coexec", "coexec-multi", "roofline",
           "traffic", "cluster"])
    args = ap.parse_args()
    if args.list:
        print(registry_listing())
        return
    try:
        spec = spec_from_args(args, base=default_serve_spec()).validate()
    except (KeyError, ValueError) as e:
        ap.error(str(e))
    use_compile_cache()

    def coexec_suite():
        print(f"# coexec device: {device_line()}", file=sys.stderr)
        structured = hetero_bench.coexec_structured_rows(spec,
                                                         smoke=args.smoke)
        write_bench_doc(args.bench_json, "coexec", spec, structured)
        return hetero_bench.run_coexec(spec, structured=structured)

    def coexec_multi_suite():
        structured = hetero_bench.coexec_multi_structured_rows(
            spec, smoke=args.smoke)
        write_bench_doc(args.bench_multi_json, "coexec-multi", spec,
                        structured)
        return hetero_bench.run_coexec_multi(spec, structured=structured)

    def kernels_suite():
        structured = kernel_micro.structured_rows(smoke=args.smoke)
        write_bench_doc(args.bench_kernels_json, "kernels", spec,
                        structured)
        return kernel_micro.run(structured=structured)

    def traffic_suite():
        structured = traffic_bench.structured_rows(spec, smoke=args.smoke)
        write_bench_doc(args.bench_traffic_json, "traffic",
                        traffic_bench.base_spec(spec, smoke=args.smoke),
                        structured)
        return traffic_bench.run(spec, structured=structured)

    def cluster_suite():
        structured = cluster_bench.structured_rows(spec, smoke=args.smoke)
        write_bench_doc(args.bench_cluster_json, "cluster", spec,
                        structured)
        return cluster_bench.run(spec, structured=structured)

    suites = dict(paper_figs.ALL)
    suites["kernels"] = kernels_suite
    suites["hetero"] = hetero_bench.run
    suites["coexec"] = coexec_suite
    suites["coexec-multi"] = coexec_multi_suite
    suites["roofline"] = roofline_table.run
    suites["traffic"] = traffic_suite
    suites["cluster"] = cluster_suite

    wanted = args.suites or list(suites)
    unknown = [key for key in wanted if key not in suites]
    print("name,value,derived")
    for key in wanted:
        if key not in suites:
            print(f"# unknown suite {key}; have {sorted(suites)}",
                  file=sys.stderr)
            continue
        for name, value, derived in suites[key]():
            print(f"{name},{value},{derived}")
    if unknown:
        # a typo'd suite name must fail the run (CI would otherwise pass
        # silently while measuring nothing)
        raise SystemExit(2)


if __name__ == "__main__":
    main()
