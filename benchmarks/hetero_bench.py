"""Step-level co-execution benchmark: policies vs heterogeneous groups,
plus the package-level scheduler sweep on both execution paths.

`run()` is the training-loop analogue of Fig. 5: three simulated pod
groups with 1.0/0.5/0.25 relative speeds train the same tiny LM; each
policy's mean step time (barrier = slowest group) and its final assignment
are reported. HGuided should approach the optimal 4:2:1 split; Static
(equal hints) stays at the imbalanced 1:1:1.

`run_coexec()` sweeps all four package schedulers — static / dynamic /
hguided / work_stealing — against each other on the DES (paper workload
profiles, virtual time) AND on the real persistent CoexecEngine (concurrent
`launch_async` requests, wall time), so a regression in either path shows
up in the same CSV.

`run_coexec_multi()` sweeps the *admission layer*: 1–32 concurrent
tenants, FIFO vs weighted-fair queueing, fused vs unfused, preemptive
pull-capping on vs off, reporting p50/p99 latency, Jain fairness over
per-tenant throughput, the time-sampled service fairness curve and
dispatched package counts on the deterministic multi-launch DES (which
drives the same `repro.core.exec.ExecutionLoop` as the real engine).
"""
from __future__ import annotations

import numpy as np
import jax

from repro.configs import get_config
from repro.data import DataPipeline
from repro.hetero import HeteroTrainer, make_policy
from repro.models import build_model
from repro.optim import AdamW

SPEEDS = {"podA": 1.0, "podB": 0.5, "podC": 0.25}
STEPS = 24
MICROBATCHES = 14

def coexec_structured_rows(spec=None, *, smoke: bool = False) -> list[dict]:
    """The coexec suite as machine-readable dicts (the JSON artifact).

    One dict per (substrate, workload/kernel, policy, memory model) with
    throughput plus the data plane's dispatch and staging-copy counters —
    what `benchmarks.run` serializes into ``BENCH_coexec.json`` so the
    perf trajectory is tracked across PRs. The real path sweeps both
    memory models; ``smoke`` shrinks sizes for CI.
    """
    from repro.launch.serve import (coexec_real_rows, coexec_sim_rows,
                                    default_serve_spec)

    base = spec if spec is not None else default_serve_spec()
    rows: list[dict] = []
    # serial vs pipelined per-unit dispatch is always part of the sweep
    # (depth 1 vs 2, plus the spec's own depth if deeper) so the JSON
    # artifact tracks what overlap buys across PRs
    depths = tuple(sorted({1, 2, int(base.units.pipeline_depth)}))
    # simulated path: one regular + one irregular paper workload, both
    # memory cost models (USM vs BUFFERS is now an end-to-end axis)
    for wl_name in ("taylor", "mandelbrot"):
        for mem in ("usm", "buffers"):
            for depth in depths:
                wl_spec = base.replace(
                    workload=base.workload.replace(name=wl_name),
                    memory=base.memory.replace(model=mem),
                    units=base.units.replace(pipeline_depth=depth))
                for r in coexec_sim_rows(wl_spec):
                    rows.append(dict(
                        kind="sim", workload=wl_name, memory=mem,
                        pipeline_depth=depth,
                        **{k: r[k] for k in
                           ("policy", "seconds", "packages",
                            "balance", "steals", "dispatches",
                            "h2d_copies", "d2h_copies",
                            "device_idle_frac", "host_overhead_frac")}))
    # real path: concurrent launch_async requests on the engine, both
    # data planes × pipeline depths, serving the workload's registered
    # kernel. Units are shared across the sweep so each kernel
    # jit-compiles once (depth is an engine property, not a unit one).
    items, requests = (1 << 12, 4) if smoke else (1 << 14, 8)
    units = base.build_units()
    for mem in ("usm", "buffers"):
        for depth in depths:
            real_spec = base.replace(
                workload=base.workload.replace(
                    name="taylor", items=items, requests=requests,
                    concurrent=requests),
                memory=base.memory.replace(model=mem),
                units=base.units.replace(pipeline_depth=depth))
            for r in coexec_real_rows(real_spec, units=units):
                rows.append(dict(
                    kind="real", workload=r["kernel"],
                    pipeline_depth=depth,
                    **{k: r[k] for k in
                       ("kernel", "memory", "policy", "requests", "n",
                        "seconds", "packages", "req_per_s", "items_per_s",
                        "dispatches", "h2d_copies", "d2h_copies",
                        "p50_ms", "p99_ms")}))
    return rows


def run_coexec(spec=None, *, smoke: bool = False, structured=None):
    """Package-scheduler sweep: DES (sim) and persistent engine (real).

    The measurement loops live in `repro.launch.serve` (shared with the
    `serve --coexec {real,sim}` CLI); this wrapper formats the structured
    rows of :func:`coexec_structured_rows` as CSV (pass ``structured`` to
    format pre-measured rows instead of re-running). `spec` is an
    optional `repro.api.CoexecSpec` base — `benchmarks.run` builds it
    from its spec-derived CLI flags.
    """
    if structured is None:
        structured = coexec_structured_rows(spec, smoke=smoke)
    rows = []
    for r in structured:
        depth = r.get("pipeline_depth", 1)
        if r["kind"] == "sim":
            rows.append((f"coexec-sim/{r['workload']}/{r['policy']}"
                         f"/{r['memory']}/d{depth}",
                         round(r["seconds"] * 1e3, 1),
                         f"packages={r['packages']};"
                         f"balance={r['balance']:.2f};"
                         f"steals={r['steals']};"
                         f"h2d={r['h2d_copies']};d2h={r['d2h_copies']};"
                         f"idle={r['device_idle_frac']:.2f}"))
        else:
            rows.append((f"coexec-real/{r['kernel']}/{r['policy']}"
                         f"/{r['memory']}/d{depth}",
                         round(r["seconds"] * 1e3, 1),
                         f"requests={r['requests']};"
                         f"packages={r['packages']};"
                         f"req_per_s={r['req_per_s']:.1f};"
                         f"h2d={r['h2d_copies']};d2h={r['d2h_copies']};"
                         f"p99_ms={r['p99_ms']:.1f}"))
    return rows


def coexec_multi_structured_rows(spec=None, *, smoke: bool = False
                                 ) -> list[dict]:
    """The coexec-multi sweep as machine-readable dicts (JSON artifact).

    One dict per (tenant count, intra-launch policy, admission policy,
    fusion mode, preemption mode) on the deterministic multi-launch DES —
    what `benchmarks.run` serializes into ``BENCH_coexec_multi.json``.
    The preemption axis sweeps {off,on} under WFQ (the `hguided` policy
    rows are where the fairness-curve tightening shows: large early
    packages are exactly what pull-capping preempts); ``smoke`` shrinks
    the tenant axis for CI.
    """
    from repro.launch.serve import coexec_multi_rows, default_serve_spec

    base = spec if spec is not None else default_serve_spec()
    base = base.replace(workload=base.workload.replace(name="taylor"))
    tenants = (1, 8, 32) if smoke else (1, 2, 4, 8, 16, 32)
    return coexec_multi_rows(base, tenants=tenants,
                             policies=("dynamic", "hguided"),
                             admissions=("fifo", "wfq"),
                             fuse_modes=(False, True),
                             preempt_modes=(False, True))


def run_coexec_multi(spec=None, *, smoke: bool = False, structured=None):
    """Admission sweep: tenants x {fifo,wfq} x {fuse} x {preempt}.

    Rows are `coexec-multi/<workload>/<policy>/<N>t/<admission>[+fuse]
    [+preempt]` with the p99 latency (ms) as the value and p50/fairness/
    fairness-curve/packages derived. Deterministic (DES virtual time):
    safe as a CI-tracked artifact (pass ``structured`` to format
    pre-measured rows instead of re-running).
    """
    if structured is None:
        structured = coexec_multi_structured_rows(spec, smoke=smoke)
    rows = []
    for r in structured:
        tag = (f"{r['admission']}{'+fuse' if r['fuse'] else ''}"
               f"{'+preempt' if r['preempt'] else ''}")
        rows.append((f"coexec-multi/{r['workload']}/{r['policy']}"
                     f"/{r['tenants']}t/{tag}",
                     round(r["p99_ms"], 2),
                     f"p50_ms={r['p50_ms']:.2f};"
                     f"fairness={r['fairness']:.3f};"
                     f"curve={r['fairness_curve_mean']:.3f};"
                     f"packages={r['packages']};"
                     f"fused_batches={r['fused_batches']}"))
    return rows


def run():
    rows = []
    cfg = get_config("qwen3-0.6b").reduced()
    model = build_model(cfg)
    params0 = model.init(jax.random.PRNGKey(0))
    for policy_name in ("static", "dyn5", "dynamic", "hguided"):
        pipe = DataPipeline(seed=3, global_batch=MICROBATCHES,
                            seq_len=32, vocab=cfg.vocab_size,
                            num_shards=MICROBATCHES)
        policy = make_policy(policy_name, {k: 1.0 for k in SPEEDS},
                             total_steps=STEPS)
        tr = HeteroTrainer(model, params0, optimizer=AdamW(lr=1e-3),
                           policy=policy, pipeline=pipe,
                           group_speeds=SPEEDS,
                           total_microbatches=MICROBATCHES)
        reports = tr.run(STEPS)
        tail = reports[STEPS // 2:]
        mean_step = float(np.mean([r.step_seconds for r in tail]))
        per_group = {g: float(np.mean([r.group_seconds[g] for r in tail
                                       if g in r.group_seconds]))
                     for g in tr.monitor.alive()}
        balance = min(per_group.values()) / max(per_group.values())
        assignment = reports[-1].assignment
        rows.append((f"hetero/{policy_name}",
                     round(mean_step * 1e3, 1),
                     f"balance={balance:.2f};assign={assignment};"
                     f"compiles={tr.exec_cache.compilations};"
                     f"loss={reports[-1].loss:.3f}"))
    return rows
