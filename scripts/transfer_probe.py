#!/usr/bin/env python3
"""Time host-device transfers and one dispatch on the local device.

    python scripts/transfer_probe.py [--min-log2 16] [--max-log2 25]
                                     [--repeats 5] [--packages] [--out FILE]

For 1-D f32 arrays of ``2**k`` items it times ``jax.device_put`` of a
host array (H2D) and ``np.asarray`` of a device array that a jitted
program produced (D2H, the layout the engine's kernels return), then
fits ``seconds = fixed + bytes / rate`` per direction by least squares
over the medians. It also times ``JaxUnit.dispatch`` of the registered
mandelbrot kernel on a pre-compiled bucket, and with ``--packages``,
for packages of three quarters of each bucket, the whole BUFFERS path
of one package run as its power-of-two bucket against the same package
run as two exact-cover pieces, with the fit of the difference against
the pad bytes the split leaves out. The fitted costs are what ``repro.core.dataplane._MIN_SPLIT_BYTES`` is
derived from. Prints one JSON object (and writes it to ``--out``).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))


def _fit(points: list[tuple[int, float]]) -> dict:
    """Least-squares ``seconds = fixed + nbytes / rate``."""
    xs = [float(b) for b, _ in points]
    ys = [s for _, s in points]
    mx, my = statistics.fmean(xs), statistics.fmean(ys)
    slope = (sum((x - mx) * (y - my) for x, y in zip(xs, ys))
             / sum((x - mx) ** 2 for x in xs))
    return {"fixed_us": (my - slope * mx) * 1e6,
            "rate_gb_s": 1e-9 / slope if slope > 0 else None}


def _median_s(fn, repeats: int) -> float:
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--min-log2", type=int, default=16)
    ap.add_argument("--max-log2", type=int, default=25)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--packages", action="store_true",
                    help="also time whole packages, bucket against pieces")
    ap.add_argument("--out", default="",
                    help="also write the JSON object to this file")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import build_kernel
    from repro.core import dataplane
    from repro.core.package import Package, Range
    from repro.core.units import JaxUnit

    dev = jax.devices()[0]
    produce = jax.jit(lambda x: x * 1.0)
    h2d, d2h, rows = [], [], []
    for k in range(args.min_log2, args.max_log2 + 1):
        n = 1 << k
        host = np.random.default_rng(k).random(n, dtype=np.float32)
        jax.device_put(host, dev).block_until_ready()        # warm
        t_h2d = _median_s(
            lambda: jax.device_put(host, dev).block_until_ready(),
            args.repeats)
        src = jax.device_put(host, dev)
        produce(src).block_until_ready()                     # compile
        samples = []
        for _ in range(args.repeats):
            out = produce(src)
            out.block_until_ready()
            t0 = time.perf_counter()
            np.asarray(out)
            samples.append(time.perf_counter() - t0)
        t_d2h = statistics.median(samples)
        h2d.append((4 * n, t_h2d))
        d2h.append((4 * n, t_d2h))
        rows.append({"items": n, "bytes": 4 * n, "h2d_us": t_h2d * 1e6,
                     "d2h_us": t_d2h * 1e6})

    kernel = build_kernel("mandelbrot")
    unit = JaxUnit("probe", dev)
    plane = dataplane.make_plane(dataplane.MemoryModel.BUFFERS)
    total = 1 << (args.max_log2 if args.packages else args.min_log2)
    rng = np.random.default_rng(0)
    inputs = [rng.uniform(-2.2, 0.8, total).astype(np.float32),
              rng.uniform(-1.4, 1.4, total).astype(np.float32)]
    plan = plane.plan(kernel, inputs, np.zeros(total, np.float32), total)
    plane.prewarm([unit], plan, 1 << args.min_log2)
    staged = [jax.device_put(a[:1 << args.min_log2], dev) for a in inputs]
    unit.dispatch(kernel.fn, 0, staged).block_until_ready()
    t_dispatch = _median_s(lambda: unit.dispatch(kernel.fn, 0, staged),
                           max(args.repeats, 20))

    # one package of 3/4 of each bucket: as one piece on that bucket, or
    # as two of a half and a quarter, which leaves a quarter of pad out
    packages = []
    for k in range(args.min_log2 + 2, args.max_log2 + 1) \
            if args.packages else ():
        size = (1 << (k - 1)) + (1 << (k - 2))
        row = {"items": size, "saved_bytes": (1 << (k - 2)) * plan.item_bytes}
        saved = dataplane._MIN_SPLIT_BYTES
        for label, min_split in (("bucket", float("inf")), ("pieces", 0)):
            dataplane._MIN_SPLIT_BYTES = min_split
            try:
                samples = []
                for r in range(args.repeats + 1):
                    pkg = Package(Range(0, size), seq=r, unit=0)
                    t0 = time.perf_counter()
                    plane.execute(unit, plan, pkg)
                    samples.append(time.perf_counter() - t0)
            finally:
                dataplane._MIN_SPLIT_BYTES = saved
            row[f"{label}_ms"] = statistics.median(samples[1:]) * 1e3
        packages.append(row)
    # (bucket - pieces) = per-byte saving * saved bytes - cost of a split
    fit = (_fit([(r["saved_bytes"], (r["bucket_ms"] - r["pieces_ms"]) * 1e-3)
                 for r in packages]) if len(packages) > 1 else None)
    if fit is not None:
        fit = {"split_cost_us": -fit["fixed_us"],
               "saving_gb_s": fit["rate_gb_s"]}

    result = {"device": {"platform": dev.platform,
                         "kind": dev.device_kind},
              "h2d": _fit(h2d), "d2h": _fit(d2h),
              "dispatch_us": t_dispatch * 1e6,
              "offset_program_us": _median_s(
                  lambda: jnp.int32(12345), max(args.repeats, 20)) * 1e6,
              "sizes": rows, "packages": packages, "split_fit": fit}
    text = json.dumps(result, indent=1)
    print(text)
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
