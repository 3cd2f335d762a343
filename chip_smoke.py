"""Chip smoke test: the co-execution engine end to end on a TPU.

    python chip_smoke.py [--seed N]
    python chip_smoke.py --four-chips [--seed N]

Everything runs in this one process; it starts no other. Without options
it needs one chip and runs, in order:

1. **Device check.** ``jax.devices()[0].platform`` must be ``tpu`` and
   the ``auto`` kernel implementation must resolve to Pallas; otherwise
   the script exits non-zero naming the platform it found.
2. **The paper's six kernels at their Table-1 sizes** (arXiv:2106.01726,
   ``repro.core.workloads.SPECS``), with lane-aligned widths: mandelbrot
   over 70.3M points, gaussian over a 5120x5120 image split by rows with
   a 2-row halo, matmul of two 4864x4864 matrices (A split by rows, B
   broadcast), ray over 9.4M rays, taylor over 1.0M items and rap over
   0.5M rows of the registered width 48. Each goes through
   ``CoexecutorRuntime.from_spec`` with ``build_kernel(name)`` on the
   serve CLI's two units on chip 0, with ``granularity`` set to Table 1's
   local work size, as concurrent ``launch_async`` calls on both the
   ``usm`` and ``buffers`` data planes. Mandelbrot also runs under every
   registered policy.
3. **Reference check.** Every result is compared with
   ``repro.kernels.ref`` jitted on the chip, at the README's tolerances;
   mandelbrot must match exactly. Matmul's tolerance applies to the
   largest entry (max-norm relative error): summing 4864 f32 terms in
   another order moves entries near zero by about 1e-4.
4. **The serve entry.** One ``coexec_real_rows`` call serves mandelbrot
   at 70.3M items.

``--four-chips`` runs only this phase, on a four-chip host: mandelbrot
at its Table-1 size under ``hguided`` and ``dynamic`` on four units, one
per chip, and the same launch on one unit on chip 0. Both results must
equal the reference exactly, every unit must serve a package, and each
unit's outputs must live on its own chip.

Times printed are smoke timings of one run — set-up (which includes
compilation) apart from serving — not benchmark metrics. The last line
of standard output is ``{"ok": true, "device": {...}}``, printed only
when every phase passed; any failure exits non-zero without it. Inputs
are drawn from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

PLANES = ("usm", "buffers")
LAUNCHES = 2            # concurrent launch_async calls per phase
#: (rtol, atol) against repro.kernels.ref — the README's table
TOLERANCES = {"taylor": (1e-5, 1e-6), "gaussian": (1e-5, 1e-5),
              "matmul": (2e-5, 2e-5), "mandelbrot": (0.0, 0.0),
              "ray": (1e-3, 1e-4), "rap": (1e-5, 1e-5)}
KERNELS = tuple(TOLERANCES)


def say(msg: str) -> None:
    """One progress line (every line but the last result line)."""
    print(f"[smoke] {msg}", flush=True)


def timing(got: dict) -> str:
    """The set-up and serve wall times of :func:`run_launches`, labelled."""
    return (f"smoke timing (chip run, not a benchmark metric): "
            f"setup_s={got['setup_s']:.3f} serve_s={got['serve_s']:.3f}")


def lane_side(items: int) -> int:
    """Side of a square with about ``items`` cells, a multiple of 128."""
    return 128 * round(math.sqrt(items) / 128)


def table1_inputs(name: str, seed: int) -> tuple[int, list]:
    """The index-space size and host inputs of one Table-1 launch.

    Args:
        name: one of the six paper kernels.
        seed: RNG seed of the inputs.

    Returns:
        ``(n, inputs)``: ``n`` is the split extent (rows for gaussian
        and matmul, items otherwise).
    """
    from repro.api import kernel_demo_inputs
    from repro.core.workloads import SPECS

    items = SPECS[name].work_items
    if name in ("gaussian", "matmul"):
        side = lane_side(items)
        rng = np.random.default_rng(seed)
        mats = [rng.standard_normal((side, side), np.float32)
                for _ in range(1 if name == "gaussian" else 2)]
        return side, mats
    return items, kernel_demo_inputs(name, items, seed=seed)


def reference(name: str, inputs: list) -> np.ndarray:
    """``repro.kernels.ref`` for one launch, jitted on the default chip."""
    import jax

    from repro.kernels import demo_spheres, ref

    fns = {"taylor": ref.taylor_sin, "gaussian": ref.gaussian_blur,
           "matmul": ref.matmul, "mandelbrot": ref.mandelbrot,
           "rap": ref.rap,
           "ray": lambda dx, dy, dz: ref.raytrace(dx, dy, dz,
                                                  demo_spheres())}
    # the f32 oracle: the TPU's default f32 matmul takes bf16 passes
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fns[name])(*inputs))


def compare(name: str, got: np.ndarray, want: np.ndarray) -> tuple:
    """Check one result against its reference at the kernel's tolerance.

    Returns:
        ``(ok, detail)`` where ``detail`` names the mismatch count and
        the largest absolute error.
    """
    rtol, atol = TOLERANCES[name]
    if got.shape != want.shape:
        return False, f"shape {got.shape} != {want.shape}"
    if not np.all(np.isfinite(got)):
        return False, f"{int(np.sum(~np.isfinite(got)))} non-finite values"
    err = np.abs(got.astype(np.float64) - want)
    if name == "matmul":
        # two correct f32 matmuls that sum K=4864 terms in different
        # orders differ by ~1e-4 on entries near zero, so every entry is
        # held to rtol of the largest |want| (max-norm relative error)
        bad = int(np.sum(err > rtol * float(np.max(np.abs(want)))))
    else:
        bad = int(np.sum(~np.isclose(got, want, rtol=rtol, atol=atol)))
    return bad == 0, f"mismatches={bad} max_abs_err={float(np.max(err))!r}"


def run_launches(spec, units, name: str, n: int, datas: list) -> dict:
    """One warm-up launch, then one concurrent ``launch_async`` per input.

    Returns:
        ``outs`` (host results), ``stats`` (their ``LaunchStats``), and
        the ``setup_s`` / ``serve_s`` wall times.
    """
    from repro.api import build_kernel
    from repro.core import CoexecutorRuntime

    kernel = build_kernel(name)
    with CoexecutorRuntime.from_spec(spec, units=units) as rt:
        t0 = time.perf_counter()
        rt.launch(n, kernel, datas[0])         # compiles every bucket
        t1 = time.perf_counter()
        handles = [rt.launch_async(n, kernel, d, tenant=f"t{i}")
                   for i, d in enumerate(datas)]
        outs = [h.result() for h in handles]
        t2 = time.perf_counter()
    return {"outs": outs, "stats": [h.stats for h in handles],
            "setup_s": t1 - t0, "serve_s": t2 - t1}


def phase_spec(policy: str, memory: str, granularity: int, *, base=None):
    """``base`` (default: the serve CLI's spec) at one policy and plane."""
    from repro.launch.serve import default_serve_spec

    base = base if base is not None else default_serve_spec()
    return base.replace(
        scheduler=base.scheduler.replace(policy=policy,
                                         granularity=granularity),
        memory=base.memory.replace(model=memory)).validate()


def check_device(want_count: int) -> dict:
    """Phase 1: refuse anything but a TPU with the Pallas default.

    Raises:
        SystemExit: not a TPU, too few chips, or ``auto`` is not Pallas.
    """
    from repro.kernels import resolve_impl
    from repro.launch.device import device_info

    dev = device_info()
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX runs on platform "
              f"{dev['platform']!r}", file=sys.stderr)
        raise SystemExit(1)
    if dev["count"] < want_count:
        print(f"chip_smoke: needs {want_count} chips, found "
              f"{dev['count']}", file=sys.stderr)
        raise SystemExit(1)
    impl = resolve_impl("auto")
    if impl != "pallas":
        print(f"chip_smoke: kernel impl 'auto' resolved to {impl!r}, not "
              f"'pallas'", file=sys.stderr)
        raise SystemExit(1)
    return dev


def kernel_phases(seed: int, units) -> list:
    """Phases 2 and 3: the six kernels on both planes, against ``ref``.

    Returns:
        Names of the failed phases (empty when all passed).
    """
    from repro.api import scheduler_names
    from repro.core.workloads import SPECS

    failed = []
    for name in KERNELS:
        lws = SPECS[name].local_work_size
        t0 = time.perf_counter()
        sets = [table1_inputs(name, seed + i) for i in range(LAUNCHES)]
        n = sets[0][0]
        datas = [ins for _, ins in sets]
        wants = [reference(name, ins) for ins in datas]
        say(f"{name}: n={n} inputs={[a.shape for a in datas[0]]} "
            f"granularity={lws}; inputs and reference "
            f"{time.perf_counter() - t0:.3f}s")
        policies = scheduler_names() if name == "mandelbrot" \
            else ("hguided",)
        for policy in policies:
            for memory in PLANES:
                tag = f"{name}/{policy}/{memory}"
                try:
                    got = run_launches(phase_spec(policy, memory, lws),
                                       units, name, n, datas)
                except Exception as exc:  # report every phase, then fail
                    say(f"{tag}: FAILED {type(exc).__name__}: {exc}")
                    failed.append(tag)
                    continue
                checks = [compare(name, o, w)
                          for o, w in zip(got["outs"], wants)]
                ok = all(c[0] for c in checks)
                per_unit = [sorted({p.unit for p in s.packages})
                            for s in got["stats"]]
                say(f"{tag}: {'ok' if ok else 'MISMATCH'} "
                    f"launches={len(datas)} "
                    f"packages={[s.num_packages for s in got['stats']]} "
                    f"units={per_unit} "
                    f"{'; '.join(c[1] for c in checks)}; {timing(got)}")
                if not ok:
                    failed.append(tag)
    return failed


def serve_phase(units) -> list:
    """Phase 4: one ``coexec_real_rows`` call, mandelbrot at 70.3M.

    Returns:
        ``["serve"]`` when the call failed or served less than asked.
    """
    from repro.core.workloads import SPECS
    from repro.launch.serve import coexec_real_rows

    n = SPECS["mandelbrot"].work_items
    spec = phase_spec("hguided", "usm", SPECS["mandelbrot"].local_work_size)
    spec = spec.replace(workload=spec.workload.replace(
        name="mandelbrot", items=n, requests=LAUNCHES,
        concurrent=LAUNCHES)).validate()
    t0 = time.perf_counter()
    try:
        (row,) = coexec_real_rows(spec, policies=("hguided",), units=units)
    except Exception as exc:  # report, then fail
        say(f"serve: FAILED {type(exc).__name__}: {exc}")
        return ["serve"]
    ok = (row["requests"] == LAUNCHES and row["impl"] == "pallas"
          and row["packages"] > 0 and math.isfinite(row["p99_ms"]))
    say(f"serve: {'ok' if ok else 'FAILED'} coexec_real_rows "
        f"{row['kernel']}[{row['impl']}]/{row['policy']}/{row['memory']} "
        f"n={row['n']} requests={row['requests']} "
        f"packages={row['packages']}; smoke timing (chip run, not a "
        f"benchmark metric): call_s={time.perf_counter() - t0:.3f} "
        f"serve_s={row['seconds']:.3f}")
    return [] if ok else ["serve"]


def four_chip_phase(seed: int) -> list:
    """``--four-chips``: mandelbrot on one unit per chip against one chip.

    Returns:
        Names of the failed checks (empty when all passed).
    """
    import jax

    from repro.api import CoexecSpec
    from repro.core import counits_from_devices
    from repro.core.units import JaxUnit
    from repro.core.workloads import SPECS

    placed: dict[str, set] = {}

    class PlacedUnit(JaxUnit):
        """Records the chips each dispatched output lives on."""

        def dispatch(self, fn, offset, args):
            out = super().dispatch(fn, offset, args)
            placed.setdefault(self.name, set()).update(
                d.id for d in out.devices())
            return out

    def units_on(devices):
        return [PlacedUnit(u.name, u.device, kind=u.kind)
                for u in counits_from_devices(devices)]

    devices = jax.devices()[:4]
    four, one = units_on(devices), units_on(devices[:1])
    lws = SPECS["mandelbrot"].local_work_size
    t0 = time.perf_counter()
    n, datas = table1_inputs("mandelbrot", seed)
    want = reference("mandelbrot", datas)
    say(f"mandelbrot: n={n} granularity={lws}; inputs and reference "
        f"{time.perf_counter() - t0:.3f}s")
    base = CoexecSpec()                 # one unit per given device
    failed = []
    for policy in ("hguided", "dynamic"):
        for memory in PLANES:
            spec = phase_spec(policy, memory, lws, base=base)
            results = {}
            for label, units in (("4 chips", four), ("chip 0", one)):
                tag = f"mandelbrot/{policy}/{memory}/{label}"
                placed.clear()
                try:
                    got = run_launches(spec, units, "mandelbrot", n, [datas])
                except Exception as exc:  # report every phase, then fail
                    say(f"{tag}: FAILED {type(exc).__name__}: {exc}")
                    failed.append(tag)
                    continue
                out, stats = got["outs"][0], got["stats"][0]
                results[label] = out
                served = {p.unit for p in stats.packages}
                ok, detail = compare("mandelbrot", out, want)
                ok = ok and served == set(range(len(units)))
                chips = {u.name: sorted(placed.get(u.name, ()))
                         for u in units}
                ok = ok and all(chips[u.name] == [u.device.id]
                                for u in units)
                say(f"{tag}: {'ok' if ok else 'FAILED'} {detail}; "
                    f"packages={stats.num_packages} "
                    f"units_served={sorted(served)} output_chips={chips}; "
                    f"{timing(got)}")
                if not ok:
                    failed.append(tag)
            if len(results) == 2:
                same = np.array_equal(results["4 chips"], results["chip 0"])
                say(f"mandelbrot/{policy}/{memory}: 4 chips "
                    f"{'==' if same else '!='} chip 0")
                if not same:
                    failed.append(f"mandelbrot/{policy}/{memory}/equal")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="RNG seed of the inputs (default: %(default)s)")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip co-execution phase")
    args = ap.parse_args(argv)

    dev = check_device(4 if args.four_chips else 1)
    from repro.launch.device import use_compile_cache

    say(f"compilation cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        failed = four_chip_phase(args.seed)
    else:
        from repro.launch.serve import default_serve_spec

        units = default_serve_spec().build_units()   # two units, chip 0
        failed = kernel_phases(args.seed, units) + serve_phase(units)
    say(f"total {time.perf_counter() - t0:.3f}s")
    if failed:
        say(f"FAILED phases: {', '.join(failed)}")
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
