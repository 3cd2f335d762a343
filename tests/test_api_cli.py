"""Spec-derived CLI flags: serve and benchmarks.run round trips.

Both CLIs generate their co-execution flags from the CoexecSpec fields
(repro.api.cli), so these tests pin the contract that makes that safe:
args → spec → args → spec is the identity for both parsers, every spec
field is reachable from the command line, and the parsers stay in sync
with the spec schema automatically.
"""
import pytest

from _propcheck import given, settings, st

from repro.api import (CoexecSpec, add_spec_args, args_from_spec,
                       spec_from_args)


def serve_parser():
    from repro.launch.serve import build_parser

    return build_parser()


def bench_parser():
    from benchmarks.run import build_parser

    return build_parser(["coexec"])


def roundtrip(parser, argv, base=None):
    spec = spec_from_args(parser.parse_args(argv), base=base)
    argv2 = args_from_spec(spec, base=base or CoexecSpec())
    spec2 = spec_from_args(parser.parse_args(argv2), base=base)
    return spec, spec2


SERVE_STYLE_ARGV = [
    [],
    ["--policy", "work_stealing", "--n", "16384"],
    ["--admission", "wfq", "--fuse", "--tenants", "16"],
    ["--policy", "dynamic", "--scheduler-opt", "num_packages=32",
     "--granularity", "64"],
    ["--workload", "mandelbrot", "--size-scale", "0.5",
     "--memory", "buffers"],
    ["--kernel", "rap", "--memory", "buffers", "--n", "2048"],
    ["--units", "2", "--unit-kinds", "cpu,gpu", "--speed-hints", "0.4,0.6",
     "--dist", "0.35"],
    ["--max-inflight", "8", "--fuse-threshold", "2048", "--fuse-limit",
     "16", "--fuse-wait-s", "0.0", "--quantum", "512"],
    ["--requests", "4", "--concurrent", "2"],
    ["--kernel-impl", "pallas", "--kernel", "taylor"],
    ["--kernel-impl", "ref", "--workload", "gaussian"],
]


@pytest.mark.parametrize("argv", SERVE_STYLE_ARGV)
def test_serve_cli_spec_cli_round_trip(argv):
    spec, spec2 = roundtrip(serve_parser(), argv)
    assert spec == spec2


@pytest.mark.parametrize("argv", SERVE_STYLE_ARGV)
def test_benchmarks_cli_spec_cli_round_trip(argv):
    parser = bench_parser()
    spec, spec2 = roundtrip(parser, ["coexec"] + argv)
    assert spec == spec2
    # suites positional coexists with the derived flags
    assert parser.parse_args(["coexec"] + argv).suites == ["coexec"]


def test_serve_cli_round_trip_with_serve_base():
    """Round trip holds over serve's non-default base spec too."""
    from repro.launch.serve import default_serve_spec

    base = default_serve_spec()
    parser = serve_parser()
    argv = ["--policy", "hguided", "--admission", "wfq", "--n", "4096"]
    spec = spec_from_args(parser.parse_args(argv), base=base)
    assert spec.units == base.units          # base fields survive
    assert spec.scheduler.policy == "hguided"
    argv2 = args_from_spec(spec, base=base)
    assert spec_from_args(parser.parse_args(argv2), base=base) == spec


@settings(max_examples=20)
@given(policy=st.sampled_from(("static", "dynamic", "hguided",
                               "work_stealing", "all")),
       admission=st.sampled_from(("fifo", "wfq")),
       fuse=st.sampled_from((False, True)),
       items=st.integers(16, 1 << 18),
       tenants=st.integers(1, 32),
       granularity=st.integers(1, 128),
       max_inflight=st.integers(1, 64),
       dist=st.floats(0.1, 0.9))
def test_random_spec_regenerates_from_its_own_argv(policy, admission, fuse,
                                                   items, tenants,
                                                   granularity,
                                                   max_inflight, dist):
    spec = CoexecSpec(
        scheduler=CoexecSpec().scheduler.replace(policy=policy,
                                                 granularity=granularity),
        admission=CoexecSpec().admission.replace(policy=admission,
                                                 fuse=fuse,
                                                 max_inflight=max_inflight),
        workload=CoexecSpec().workload.replace(items=items,
                                               tenants=tenants),
        units=CoexecSpec().units.replace(dist=(dist,)),
    )
    parser = serve_parser()
    argv = args_from_spec(spec)
    assert spec_from_args(parser.parse_args(argv)) == spec


def test_bad_flag_values_error_cleanly():
    parser = serve_parser()
    with pytest.raises(SystemExit):
        parser.parse_args(["--admission", "lifo"])      # not a choice
    with pytest.raises(SystemExit):
        parser.parse_args(["--scheduler-opt", "no-equals-sign"])
    with pytest.raises(SystemExit):
        parser.parse_args(["--kernel-impl", "opencl"])  # not a choice


def test_spec_json_flag_exists():
    ns = serve_parser().parse_args(["--coexec", "sim", "--spec-json"])
    assert ns.spec_json is True


def test_list_flag_exists_on_both_clis():
    assert serve_parser().parse_args(["--list"]).list is True
    assert bench_parser().parse_args(["--list"]).list is True


def test_none_literal_resets_optional_fields_over_base():
    """Every spec is reachable from argv even over a non-default base."""
    from repro.launch.serve import default_serve_spec

    base = default_serve_spec()          # units.count=2, dist set, ...
    parser = serve_parser()
    # an all-default spec regenerates from its own argv over that base
    spec = CoexecSpec()
    argv = args_from_spec(spec, base=base)
    assert spec_from_args(parser.parse_args(argv), base=base) == spec
    # and the literal is usable by hand
    ns = parser.parse_args(["--units", "none", "--max-inflight", "none"])
    merged = spec_from_args(ns, base=base)
    assert merged.units.count is None
    assert merged.admission.max_inflight is None


def test_scheduler_opt_none_clears_base_options():
    base = CoexecSpec().replace(
        scheduler=CoexecSpec().scheduler.replace(
            policy="dynamic", options=(("num_packages", 32),)))
    parser = serve_parser()
    bare = spec_from_args(
        parser.parse_args(["--scheduler-opt", "none"]), base=base)
    assert bare.scheduler.options == ()
    # and the automatic round trip uses it: spec without options over a
    # base with options regenerates exactly
    spec = base.replace(scheduler=base.scheduler.replace(options=()))
    argv = args_from_spec(spec, base=base)
    assert spec_from_args(parser.parse_args(argv), base=base) == spec


def test_sim_rows_honor_spec_scheduler_options():
    """The DES path obeys --scheduler-opt/--granularity like the engine."""
    from repro.launch.serve import coexec_sim_rows

    spec = (CoexecSpec.builder()
            .policy("dynamic", num_packages=32)
            .workload("taylor")
            .build())
    (row,) = coexec_sim_rows(spec)
    assert row["packages"] == 32


def test_benchmarks_cli_rejects_bad_policy_cleanly():
    import os
    import pathlib
    import subprocess
    import sys

    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo / "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "coexec",
         "--policy", "tpyo"],
        capture_output=True, text=True, timeout=120, env=env, cwd=repo)
    assert proc.returncode == 2          # argparse usage error, not a crash
    assert "unknown scheduling policy" in proc.stderr
    assert "Traceback" not in proc.stderr
