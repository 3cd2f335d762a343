"""Serving launcher: batched request loop over the cached decode path,
plus a co-execution request server over the persistent CoexecEngine.

Default (LM) mode: requests are (prompt, max_tokens) pairs batched up to
--batch; generation is greedy. Reduced configs run on this host; full
configs serve via the dry-run path (compile-only proof).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --requests 16 --batch 4

Co-execution mode: each "request" is one data-parallel kernel launch
served through `CoexecutorRuntime.launch_async` on a long-lived engine —
up to --concurrent launches interleave on the same Coexecution Units.
Every co-execution flag is *derived* from the `repro.api.CoexecSpec`
fields (see `repro.api.cli`): the parsed flags fold into one spec that
drives the real engine and the DES identically, `--spec-json` dumps the
resolved spec as a reproducible artifact, and `--list` prints every
registered scheduler/workload/kernel with its declared option fields.
The served kernel is any registered kernel (`--kernel`, defaulting to
the workload's same-named kernel), `--kernel-impl {auto,pallas,xla,ref}`
picks its implementation variant (the Pallas fast path vs the compiled
XLA oracle; auto is backend-aware), and `--memory {usm,buffers}` selects
the engine's real data plane — rows report its dispatch and
staging-copy counters. `--policy all` sweeps every registered policy;
with `--coexec sim` the same sweep runs on the DES instead of real
threads; `--admission wfq` / `--fuse` / `--preempt` / `--tenants N`
switch the sim path to the multi-tenant DES sweep with p50/p99 latency,
Jain fairness and the time-sampled fairness curve per row. Both
substrates drive the one shared control plane
(`repro.core.exec.ExecutionLoop`), so `--preempt` — WFQ reclaiming
credit mid-launch by capping per-pull package sizes — behaves
identically on `--coexec real` and `--coexec sim`.

    PYTHONPATH=src python -m repro.launch.serve --coexec real \
        --policy all --requests 16 --concurrent 8 --n 65536 \
        --kernel mandelbrot --memory buffers
    PYTHONPATH=src python -m repro.launch.serve --coexec sim \
        --policy all --workload mandelbrot
    PYTHONPATH=src python -m repro.launch.serve --coexec sim \
        --admission wfq --fuse --tenants 16
"""
from __future__ import annotations

import argparse
import time


def _percentile_ms(sorted_s: list, q: float) -> float:
    """Nearest-rank percentile of sorted seconds, in milliseconds."""
    import math

    if not sorted_s:
        return float("nan")
    idx = max(0, math.ceil(q * len(sorted_s)) - 1)
    return 1e3 * sorted_s[idx]


def default_serve_spec():
    """The serve CLI's base spec: two same-device units, dist 0.4.

    Two Coexecution Units on this host's first device are the CPU-only
    container's stand-in for the paper's CPU+GPU pair; flags the user
    passes override these fields (see `repro.api.cli.spec_from_args`).
    """
    from repro.api import CoexecSpec

    return (CoexecSpec.builder()
            .policy("all")      # sweep every registered policy by default
            .units(count=2, kinds=("cpu", "cpu"), speed_hints=(0.4, 0.6))
            .dist(0.4)
            .workload("mandelbrot")
            .build())


def _sweep_policies(spec) -> tuple[str, ...]:
    """Expand ``policy="all"`` into every registered policy name."""
    from repro.api import scheduler_names

    if spec.scheduler.policy == "all":
        return scheduler_names()
    return (spec.scheduler.policy,)


def coexec_real_rows(spec=None, *, policies=None, units=None) -> list[dict]:
    """Serve ``spec.workload.requests`` kernel launches per policy through
    the persistent engine (at most ``spec.workload.concurrent`` in
    flight); one measurement dict each. Shared by ``serve --coexec real``
    and ``benchmarks.run coexec``. The spec's admission section selects
    the engine's cross-launch queueing policy; its workload section picks
    the served kernel (any registered kernel, via ``--kernel`` or the
    workload's name) and its memory section the data plane, whose
    dispatch/copy counters are aggregated into each row.
    """
    from repro.api import kernel_demo_inputs
    from repro.kernels import resolve_impl
    from ..core import CoexecutorRuntime, service_fairness_curve

    if spec is None:
        spec = default_serve_spec()
    if units is None:
        units = spec.build_units()
    n = spec.workload.items
    requests = spec.workload.requests
    concurrent = spec.workload.concurrent
    kname = spec.workload.resolve_kernel()
    impl = resolve_impl(spec.workload.kernel_impl)
    kernel = spec.workload.build_kernel()
    datas = [kernel_demo_inputs(kname, n, seed=i) for i in range(requests)]
    rows = []
    for policy in (policies or _sweep_policies(spec)):
        pspec = spec.replace(
            scheduler=spec.scheduler.replace(policy=policy))
        with CoexecutorRuntime.from_spec(pspec, units=units) as rt:
            rt.launch(n, kernel, datas[0])          # warm the jit cache
            t0 = time.perf_counter()
            served, pkgs, lats, inflight = 0, 0, [], []
            h2d, d2h, dispatches = 0, 0, 0
            service = []        # (t_complete, tenant, items) per package

            def _reap(h, t_sub, tenant):
                nonlocal served, pkgs, h2d, d2h, dispatches
                h.result()
                served, pkgs = served + 1, pkgs + h.stats.num_packages
                h2d += h.stats.data.h2d_copies
                d2h += h.stats.data.d2h_copies
                dispatches += h.stats.data.dispatches
                service.extend((p.t_complete, tenant, p.size)
                               for p in h.stats.packages)
                lats.append(time.perf_counter() - t_sub)

            for i, d in enumerate(datas):
                inflight.append((rt.launch_async(n, kernel, d,
                                                 tenant=f"t{i}"),
                                 time.perf_counter(), f"t{i}"))
                if len(inflight) >= concurrent:
                    _reap(*inflight.pop(0))
            for h, t_sub, tenant in inflight:
                _reap(h, t_sub, tenant)
            dt = time.perf_counter() - t0
        lats.sort()
        # fairness of throughput across requests + the time-sampled
        # service fairness curve (the measure --preempt tightens), on a
        # duration-weighted deterministic clock (items computed)
        from ..core import jain_index

        thru = [n / max(lat, 1e-9) for lat in lats]
        clock, ticked = 0, []
        for _, tenant, items in sorted(service):
            clock += items
            ticked.append((clock, tenant, items))
        curve = service_fairness_curve(
            ticked, [f"t{i}" for i in range(requests)])
        rows.append(dict(kernel=kname, impl=impl,
                         memory=spec.memory.model,
                         policy=policy, requests=served, n=n,
                         concurrent=concurrent, seconds=dt, packages=pkgs,
                         req_per_s=served / dt,
                         items_per_s=served * n / dt,
                         dispatches=dispatches,
                         h2d_copies=h2d, d2h_copies=d2h,
                         fairness=jain_index(thru),
                         fairness_curve_mean=float(sum(curve) / len(curve)),
                         fairness_curve_min=float(min(curve)),
                         p50_ms=_percentile_ms(lats, 0.5),
                         p99_ms=_percentile_ms(lats, 0.99)))
    return rows


def coexec_sim_rows(spec=None, *, policies=None) -> list[dict]:
    """The same policy sweep on the DES (virtual time, deterministic).

    The spec's scheduler section (options, granularity) drives the DES
    split exactly as it drives the real engine; the speed hint is the
    DES units' calibrated speeds (the profile's ground truth), not the
    spec's ``dist`` — `dist` describes real devices the DES replaces.
    """
    from ..core import paper_workload, simulate

    if spec is None:
        spec = default_serve_spec()
    workload = spec.workload.name
    wl, cpu, gpu = paper_workload(workload,
                                  size_scale=spec.workload.size_scale)
    rows = []
    for policy in (policies or _sweep_policies(spec)):
        sched = spec.scheduler.replace(policy=policy).build(
            wl.total, 2, speeds=[cpu.speed, gpu.speed])
        r = simulate(sched, [cpu, gpu], wl, spec=spec)
        busy = sum(r.unit_busy_s.values())
        span = max(r.total_s, 1e-12)
        rows.append(dict(workload=workload, policy=policy,
                         memory=r.memory,
                         seconds=r.total_s, packages=r.num_packages,
                         balance=r.balance(),
                         steals=getattr(sched, "steals", 0),
                         dispatches=r.data.dispatches,
                         h2d_copies=r.data.h2d_copies,
                         d2h_copies=r.data.d2h_copies,
                         device_idle_frac=max(
                             0.0, 1.0 - busy / (len(r.unit_busy_s) * span)),
                         host_overhead_frac=r.host_busy_s / span))
    return rows


def coexec_multi_rows(spec=None, *, tenants=None, policies=None,
                      per_tenant_items: int = 2048,
                      num_packages: int = 16,
                      admissions=None,
                      fuse_modes=None,
                      preempt_modes=None) -> list[dict]:
    """Multi-tenant admission sweep on the DES: one row per (tenant count,
    policy, admission policy, fusion mode, preemption mode) with p50/p99
    latency, Jain fairness over per-tenant throughput, the time-sampled
    service fairness curve (the measure ``--preempt`` tightens), and
    total dispatched packages. Sweep axes default to the single point the
    spec describes (its admission policy/fuse/preempt flags and
    ``workload.tenants``); pass tuples to sweep. Shared by
    ``serve --coexec sim --admission/--fuse/--preempt/--tenants`` and
    ``benchmarks.run coexec-multi``.
    """
    import numpy as np

    from ..core import (LaunchSpec, Workload, jain_index, paper_workload,
                        simulate_multi)

    if spec is None:
        spec = default_serve_spec()
    workload = spec.workload.name
    if tenants is None:
        tenants = (spec.workload.tenants or 8,)
    if admissions is None:
        admissions = (spec.admission.policy,)
    if fuse_modes is None:
        fuse_modes = (spec.admission.fuse,)
    if preempt_modes is None:
        preempt_modes = (spec.admission.preempt,)
    base, cpu, gpu = paper_workload(workload)
    per_item_in = base.bytes_in_per_item
    per_item_out = base.bytes_out_per_item
    # keep the profile's irregularity: resample its per-item weights to
    # the per-tenant problem size (as paper_workload does for size sweeps)
    weights = None
    if base.weights is not None:
        idx = np.linspace(0, len(base.weights) - 1,
                          per_tenant_items).astype(int)
        weights = base.weights[idx]

    def sched_for(policy):
        # the spec's scheduler options/granularity apply; dynamic gets a
        # per-tenant-sized package count unless the spec pins one
        sched_spec = spec.scheduler.replace(policy=policy)
        if policy == "dynamic" and \
                "num_packages" not in sched_spec.options_dict():
            sched_spec = sched_spec.with_options(num_packages=num_packages)
        return sched_spec.build(per_tenant_items, 2,
                                speeds=[cpu.speed, gpu.speed])

    def specs(nt, policy):
        out = []
        for t in range(nt):
            wl = Workload(name=base.name, total=per_tenant_items,
                          bytes_in_per_item=per_item_in,
                          bytes_out_per_item=per_item_out,
                          working_set_bytes=base.working_set_bytes
                          * per_tenant_items / base.total,
                          weights=weights,
                          contention_scale=base.contention_scale)
            out.append(LaunchSpec(wl, sched_for(policy), tenant=f"t{t}"))
        return out

    rows = []
    for policy in (policies or ("dynamic",)):
        for nt in tenants:
            for adm in admissions:
                for fuse in fuse_modes:
                    for preempt in preempt_modes:
                        if preempt and adm != "wfq" \
                                and False in preempt_modes:
                            # sweeping both modes: fifo+preempt would
                            # duplicate the fifo row (preemption only
                            # reclaims WFQ credit). A single-point
                            # request still produces its row, with the
                            # flag inert.
                            continue
                        cfg = spec.admission.replace(
                            policy=adm, fuse=fuse, preempt=preempt,
                            fuse_threshold=per_tenant_items,
                            fuse_wait_s=0.0).to_config()
                        res = simulate_multi(specs(nt, policy), [cpu, gpu],
                                             admission=cfg)
                        lats = sorted(res.latencies())
                        thru = [r.items / max(r.latency_s, 1e-12)
                                for r in res.launches]
                        curve = res.fairness_curve()
                        rows.append(dict(
                            workload=workload, tenants=nt, admission=adm,
                            fuse=fuse, preempt=preempt, policy=policy,
                            p50_ms=_percentile_ms(lats, 0.5),
                            p99_ms=_percentile_ms(lats, 0.99),
                            fairness=jain_index(thru),
                            fairness_curve_mean=float(
                                sum(curve) / len(curve)),
                            fairness_curve_min=float(min(curve)),
                            packages=res.dispatched_packages,
                            fused_batches=res.fused_batches,
                            total_ms=1e3 * res.total_s))
    return rows


def trace_from_spec(spec, capacity_items_s: float):
    """Build (or load) the open-loop trace the spec's traffic section asks
    for.

    Args:
        spec: a ``CoexecSpec`` with ``traffic.arrival != "closed"``.
        capacity_items_s: modeled serving capacity in work-items/s, used
            to turn ``traffic.load`` into an arrival rate when
            ``traffic.rate`` is 0.

    Returns:
        A :class:`repro.core.Trace`.
    """
    from ..core import Trace, synthesize_trace

    tr = spec.traffic
    if tr.trace:
        return Trace.load(tr.trace)
    items = spec.workload.items
    rate = tr.rate if tr.rate > 0 else tr.load * capacity_items_s / items
    return synthesize_trace(
        tr.arrivals, rate, arrival=tr.arrival,
        tenants=spec.workload.tenants or 8, items=items,
        item_jitter=tr.item_jitter, slo_ms=spec.admission.slo_ms,
        burst=tr.burst, burst_duty=tr.burst_duty, seed=tr.seed)


def traffic_rows(spec=None, *, loads=None, admissions=None,
                 arrival_kinds=None, tenants=None) -> list[dict]:
    """Open-loop SLO sweep on the DES: one aggregate row per (arrival
    process, load multiple, admission mode) with admitted-launch
    p50/p99 latency, deadline-miss rate, shed fraction and fusion
    counters. Sweep axes default to the single point the spec describes;
    pass tuples to sweep. Shared by ``serve --coexec sim --arrival ...``
    and ``benchmarks.run traffic``.

    Each admission mode is a dict of ``AdmissionSpec.replace`` overrides
    (e.g. ``{"policy": "edf", "preempt": True, "shed": True}``); a
    string is shorthand for ``{"policy": <string>}``.
    """
    from ..core import capacity_items_per_s, paper_workload, replay_trace_sim

    if spec is None:
        spec = default_serve_spec()
    _, cpu, gpu = paper_workload(spec.workload.name)
    units = [cpu, gpu]
    cap = capacity_items_per_s(units)
    if loads is None:
        loads = (spec.traffic.load,)
    if admissions is None:
        admissions = ({},)
    if arrival_kinds is None:
        arrival_kinds = (spec.traffic.arrival
                         if spec.traffic.arrival != "closed" else "poisson",)
    if tenants is None:
        tenants = spec.workload.tenants or 8
    rows = []
    for arrival in arrival_kinds:
        for load in loads:
            tspec = spec.replace(
                traffic=spec.traffic.replace(arrival=arrival, load=load),
                workload=spec.workload.replace(tenants=tenants))
            trace = trace_from_spec(tspec, cap)
            # a file trace describes itself; the spec's synthesis knobs
            # didn't shape it
            row_arrival = arrival
            row_tenants = tenants
            if tspec.traffic.trace:
                row_arrival = str(trace.meta.get("arrival", "trace"))
                row_tenants = len(trace.tenants())
            for mode in admissions:
                if isinstance(mode, str):
                    mode = {"policy": mode}
                adm = tspec.admission.replace(**mode)
                rep = replay_trace_sim(trace, units,
                                       admission=adm.to_config())
                r = rep.result
                rows.append(dict(
                    workload=spec.workload.name, arrival=row_arrival,
                    tenants=row_tenants, load=float(load),
                    admission=adm.policy, preempt=adm.preempt,
                    shed=adm.shed, slo_ms=adm.slo_ms,
                    arrivals=len(trace),
                    admitted=len(r.launches), shed_count=len(r.shed),
                    p50_ms=rep.p50_ms(), p99_ms=rep.p99_ms(),
                    miss_rate=rep.miss_rate(),
                    shed_fraction=rep.shed_fraction(),
                    packages=r.dispatched_packages,
                    fused_batches=r.fused_batches,
                    total_ms=1e3 * r.total_s))
    return rows


def cluster_pool_units(spec, n: int) -> list:
    """Provision ``n`` simulated pool units from the workload's pair.

    The paper's calibrated CPU/GPU units are cloned round-robin across
    the pool slots, so an elastic pool keeps the heterogeneous speed mix
    the profiles were calibrated against.
    """
    from ..core import SimUnit, paper_workload

    _, cpu, gpu = paper_workload(spec.workload.name)
    pair = (cpu, gpu)
    return [SimUnit(f"{pair[i % 2].name}{i}", pair[i % 2].kind,
                    speed=pair[i % 2].speed, alpha=pair[i % 2].alpha,
                    setup_s=pair[i % 2].setup_s) for i in range(n)]


def cluster_rows(spec=None, *, plans=None) -> list[dict]:
    """Elastic-cluster serve on the DES: one audit row per failure plan.

    Replays the spec's open-loop trace through
    :func:`repro.core.replay_trace_cluster` — the runtime-resizable pool
    with exact package re-issue — and reports the exact-once audit
    (``lost``/``duplicated`` must be 0) next to the latency percentiles.
    ``plans`` maps row names to :class:`repro.core.FailurePlan` objects
    (``None`` plans run undisturbed); it defaults to the single plan the
    spec's ``cluster.failure_plan`` names, or an undisturbed run. Shared
    by ``serve --coexec sim --cluster`` and ``benchmarks.run cluster``.
    """
    import dataclasses

    from ..core import capacity_items_per_s, replay_trace_cluster

    if spec is None:
        spec = default_serve_spec()
    if spec.traffic.arrival == "closed" and not spec.traffic.trace:
        # The cluster tier replays an open-loop trace; a closed-loop
        # spec (the CLI default) has none, so fall back to poisson
        # arrivals instead of rejecting the run.
        spec = dataclasses.replace(
            spec, traffic=dataclasses.replace(spec.traffic,
                                              arrival="poisson"))
    cl = spec.cluster
    n = cl.max_units if cl.max_units is not None else max(cl.min_units, 4)
    units = cluster_pool_units(spec, n)
    active = units[:cl.min_units]
    trace = trace_from_spec(spec, capacity_items_per_s(active))
    if plans is None:
        plans = {"plan" if cl.failure_plan else "undisturbed":
                 cl.load_plan()}
    rows = []
    for name, plan in plans.items():
        rep = replay_trace_cluster(
            trace, units, spec=spec, plan=plan,
            min_units=cl.min_units, autoscale=cl.autoscale,
            autoscale_opts=cl.autoscaler_opts(),
            granularity=spec.scheduler.granularity)
        rows.append(dict(
            name=name, workload=spec.workload.name,
            arrival=spec.traffic.arrival, admission=spec.admission.policy,
            min_units=rep.min_units, max_units=rep.max_units,
            autoscale=cl.autoscale, arrivals=rep.arrivals,
            admitted=rep.admitted, shed_count=rep.shed_count,
            completed=rep.completed, lost=rep.lost,
            duplicated=rep.duplicated, reissued=rep.reissued,
            kills=len(rep.kills), joins=len(rep.joins),
            resizes=len(rep.scale_events),
            p50_ms=rep.p50_ms(), p99_ms=rep.p99_ms()))
    return rows


def serve_coexec_cluster(spec) -> None:
    """Elastic-cluster serve: audit + latency row per failure plan."""
    for row in cluster_rows(spec):
        print(f"[serve/cluster] {row['workload']}/{row['arrival']}"
              f"/{row['admission']} pool={row['min_units']}.."
              f"{row['max_units']}"
              f"{'+autoscale' if row['autoscale'] else ''} "
              f"({row['name']}): {row['admitted']}/{row['arrivals']} "
              f"admitted, {row['completed']} completed, "
              f"lost={row['lost']} dup={row['duplicated']} "
              f"reissued={row['reissued']} kills={row['kills']} "
              f"joins={row['joins']} resizes={row['resizes']}, "
              f"p50={row['p50_ms']:.2f}ms p99={row['p99_ms']:.2f}ms")


def traffic_tenant_rows(spec=None) -> list[dict]:
    """Per-tenant serving outcome of the spec's open-loop replay: one row
    per tenant with arrivals/admitted/shed counts, p50/p99 admitted
    latency and deadline-miss rate — the serve columns the SLO work
    surfaces.
    """
    from ..core import capacity_items_per_s, paper_workload, replay_trace_sim

    if spec is None:
        spec = default_serve_spec()
    _, cpu, gpu = paper_workload(spec.workload.name)
    units = [cpu, gpu]
    trace = trace_from_spec(spec, capacity_items_per_s(units))
    rep = replay_trace_sim(trace, units, spec=spec)
    return [dict(tenant=t.tenant, arrivals=t.arrivals, admitted=t.admitted,
                 shed=t.shed, p50_ms=t.p50_ms, p99_ms=t.p99_ms,
                 miss_rate=t.miss_rate) for t in rep.rows]


def serve_coexec_traffic(spec) -> None:
    """Open-loop serve: aggregate row plus per-tenant p50/p99/miss/shed."""
    for row in traffic_rows(spec):
        print(f"[serve/traffic] {row['workload']}/{row['arrival']}"
              f"/{row['tenants']}t load={row['load']:.2f} "
              f"{row['admission']}"
              f"{'+preempt' if row['preempt'] else ''}"
              f"{'+shed' if row['shed'] else ''}: "
              f"{row['admitted']}/{row['arrivals']} admitted "
              f"(shed {row['shed_count']}), "
              f"p50={row['p50_ms']:.2f}ms p99={row['p99_ms']:.2f}ms "
              f"miss={row['miss_rate']:.3f}")
    for row in traffic_tenant_rows(spec):
        print(f"[serve/traffic]   {row['tenant']:>8s}: "
              f"arrivals={row['arrivals']:4d} admitted={row['admitted']:4d} "
              f"shed={row['shed']:3d} p50={row['p50_ms']:8.2f}ms "
              f"p99={row['p99_ms']:8.2f}ms miss={row['miss_rate']:.3f}")


def serve_coexec_real(spec) -> None:
    from .device import device_line

    print(f"[serve/coexec] device: {device_line()}")
    for row in coexec_real_rows(spec):
        print(f"[serve/coexec] {row['kernel']}[{row['impl']}]"
              f"/{row['policy']:13s} "
              f"({spec.admission.policy}"
              f"{'+fuse' if spec.admission.fuse else ''}"
              f"{'+preempt' if spec.admission.preempt else ''}"
              f"/{row['memory']}): {row['requests']} "
              f"requests ({row['concurrent']} in flight) in "
              f"{row['seconds']:.3f}s = {row['req_per_s']:6.1f} req/s, "
              f"{row['items_per_s'] / 1e6:7.2f} "
              f"Mitems/s, {row['packages']} packages, "
              f"copies h2d={row['h2d_copies']} d2h={row['d2h_copies']}, "
              f"fairness={row['fairness']:.3f} "
              f"curve={row['fairness_curve_mean']:.3f}, "
              f"p50={row['p50_ms']:.1f}ms p99={row['p99_ms']:.1f}ms")


def serve_coexec_sim(spec) -> None:
    if spec.cluster.enabled:
        return serve_coexec_cluster(spec)
    if spec.traffic.arrival != "closed" or spec.traffic.trace:
        return serve_coexec_traffic(spec)
    multi = (spec.admission.policy != "fifo" or spec.admission.fuse
             or spec.workload.tenants is not None)
    if multi:
        for row in coexec_multi_rows(spec, policies=_sweep_policies(spec)):
            print(f"[serve/coexec-multi] {row['workload']}"
                  f"/{row['policy']}/{row['tenants']}t/{row['admission']}"
                  f"{'+fuse' if row['fuse'] else ''}"
                  f"{'+preempt' if row['preempt'] else ''}: "
                  f"p50={row['p50_ms']:.2f}ms p99={row['p99_ms']:.2f}ms "
                  f"fairness={row['fairness']:.3f} "
                  f"curve={row['fairness_curve_mean']:.3f} "
                  f"packages={row['packages']} "
                  f"(fused_batches={row['fused_batches']})")
        return
    for row in coexec_sim_rows(spec):
        print(f"[serve/coexec-sim] {row['workload']}/{row['policy']:13s}: "
              f"{row['seconds']:7.3f}s, {row['packages']:4d} packages, "
              f"balance={row['balance']:.2f}, steals={row['steals']}")


def build_parser() -> argparse.ArgumentParser:
    """The serve CLI parser: LM flags + spec-derived co-execution flags.

    Returns:
        A parser whose co-execution flags are generated from the
        ``CoexecSpec`` fields by :func:`repro.api.cli.add_spec_args` —
        adding a spec field adds a serve flag with no edit here.
    """
    from repro.api import add_spec_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--coexec", choices=["off", "real", "sim"],
                    default="off",
                    help="serve co-execution kernel requests instead of LM "
                         "decode: 'real' uses the persistent CoexecEngine, "
                         "'sim' the discrete-event simulator")
    ap.add_argument("--spec-json", action="store_true",
                    help="print the resolved CoexecSpec as JSON and exit")
    ap.add_argument("--list", action="store_true",
                    help="print registered schedulers, workloads and "
                         "kernels (with their option fields) and exit")
    add_spec_args(ap)
    return ap


def main() -> None:
    from repro.api import registry_listing, spec_from_args

    ap = build_parser()
    args = ap.parse_args()
    if args.list:
        print(registry_listing())
        return
    try:
        spec = spec_from_args(args, base=default_serve_spec()).validate()
    except (KeyError, ValueError) as e:
        ap.error(str(e))

    if args.spec_json:
        print(spec.to_json(indent=2))
        return
    from .device import use_compile_cache

    use_compile_cache()
    if args.coexec == "real":
        return serve_coexec_real(spec)
    if args.coexec == "sim":
        return serve_coexec_sim(spec)

    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..models import build_model

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    step = jax.jit(model.decode_step)

    requests = spec.workload.requests
    B, P, G = args.batch, args.prompt_len, args.max_tokens
    served = 0
    t0 = time.perf_counter()
    rngs = jax.random.split(jax.random.PRNGKey(1),
                            -(-requests // B))
    for batch_id, rk in enumerate(rngs):
        n = min(B, requests - served)
        prompts = jax.random.randint(rk, (B, P), 0, cfg.vocab_size)
        cache = model.init_cache(B, P + G)
        if model.prefill is not None:
            batch = {"tokens": prompts,
                     "frames": jnp.zeros((B, cfg.encoder_seq,
                                          cfg.d_model), jnp.bfloat16)}
            cache = jax.jit(model.prefill)(params, batch, cache)
        for t in range(P):
            logits, cache = step(params, prompts[:, t:t + 1], cache)
        cur = jnp.argmax(logits[:, :cfg.vocab_size], -1)[:, None]
        for _ in range(G - 1):
            logits, cache = step(params, cur, cache)
            cur = jnp.argmax(logits[:, :cfg.vocab_size], -1)[:, None]
        jax.block_until_ready(cur)
        served += n
    dt = time.perf_counter() - t0
    print(f"[serve] {served} requests, {served * (P + G)} tokens in "
          f"{dt:.2f}s ({served * (P + G) / dt:.0f} tok/s incl. compile)")


if __name__ == "__main__":
    main()
