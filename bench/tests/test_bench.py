"""Tests of the benchmark harness, on the CPU at small sizes.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They drive the harness's own code (``bench/``) with the program under
it, but never ask for a chip: ``run.run_cell`` is called directly, and
the only runs of ``bench/run.py`` as a program are the ones that must
refuse the CPU.
"""
from __future__ import annotations

import hashlib
import json
import pathlib
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import cells, check, control, load, run, xplane  # noqa: E402

BENCH = cells.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
FIXTURE = ROOT / "bench" / "fixtures" / "mandelbrot-small.xplane.pb"
MANDEL = "mandelbrot-t1.hguided.buffers"


def small(name: str, root: pathlib.Path = ROOT) -> cells.Cell:
    """A cell as loaded, cut to a size the CPU runs in a second.

    The configuration keys that cut it are the file
    ``bench/tests/small/<config>.json``, one per configuration.

    Raises:
        FileNotFoundError: the configuration has no such file.
    """
    cell = cells.load(name, root)
    path = root / "bench" / "tests" / "small" / f"{cell.config['name']}.json"
    cell.config = dict(cell.config, **cells.load_json(path))
    return cell


# -- checks of one configuration ----------------------------------------
# Each takes a cell cut to its CPU size. The tests parametrized over the
# cells of BENCHMARK.json call them, and so does the dummy test, so a new
# configuration is held to them by its files alone.

def check_seed_reproduces_inputs(cell: cells.Cell) -> None:
    """Inputs follow the seed, and fit the kernel's declared arguments:
    each split argument spans ``items`` along its axis, and floating
    inputs have the configuration's dtype."""
    from repro.api import build_kernel
    from repro.core.dataplane import ArgRole

    cfg, ref = cell.config, cell.ref
    seed = 2**31 + 17
    a, b = ref.inputs(cfg, seed, 0), ref.inputs(cfg, seed, 0)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    specs = build_kernel(cfg["kernel"], **ref.kernel_options(cfg)).args
    assert 1 <= len(a) <= len(specs)
    for spec, x in zip(specs, a):
        if spec.role is ArgRole.SPLIT:
            assert x.shape[spec.axis] == cfg["items"], spec.name
        if np.issubdtype(x.dtype, np.floating):
            assert x.dtype == np.dtype(cfg["dtype"]), spec.name
    other = ref.inputs(cfg, seed + 1, 0)
    assert not all(np.array_equal(x, y) for x, y in zip(a, other))
    assert not all(np.array_equal(x, y)
                   for x, y in zip(a, ref.inputs(cfg, seed, 1)))


def _whole_launch(kernel, data: list) -> np.ndarray:
    """The kernel's package body over the whole index space, each split
    argument zero-padded by its halo as the data plane hands it."""
    from repro.core.dataplane import ArgRole

    chunks = []
    for spec, arr in zip(kernel.args, kernel.bind(data)):
        if spec.role is ArgRole.SPLIT and spec.halo:
            pad = [(0, 0)] * arr.ndim
            pad[spec.axis] = (spec.halo, spec.halo)
            arr = np.pad(arr, pad)
        chunks.append(arr)
    return np.asarray(kernel.fn(0, *chunks))


def check_ops_do_not_depend_on_the_implementation(cell: cells.Cell) -> None:
    """The reference's operations and bytes read the same from the XLA
    and the Pallas body's answers; the bytes hold at least the output.
    The exact rule of each is the reference's own."""
    from repro.api import build_kernel

    cfg, ref = cell.config, cell.ref
    data = ref.inputs(cfg, 9, 0)
    opts = ref.kernel_options(cfg)
    outs = [_whole_launch(build_kernel(cfg["kernel"], impl=impl, **opts),
                          data) for impl in ("xla", "pallas")]
    reads = [ref.ops(cfg, data, o) for o in outs]
    assert reads[0] == reads[1] > 0
    moved = [ref.nbytes(cfg, data, o) for o in outs]
    assert moved[0] == moved[1] >= outs[0].nbytes


def check_control_fails_where_the_reference_passes(cell: cells.Cell) -> None:
    cfg, ref = cell.config, cell.ref
    for seed in (11, 12, 13):
        ok, checks = control.control(cell, seed)
        assert not ok, checks
        data = ref.inputs(cfg, seed, 0)
        want = ref.reference(cfg, data)
        assert check.judge(cfg, check.numbers(ref, cfg, [(want, want)]))[0]


def check_small_run(cell: cells.Cell, traced: bool) -> dict:
    """One CPU run is correct and reports the cell's metrics; returns
    its result."""
    res = run.run_cell(cell, 2**31 + 99, 0.3, traced)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    want = cell.per_layer if traced else cell.end_to_end
    assert set(res["metrics"]) <= {m["name"] for m in want}
    if not traced:
        assert set(res["metrics"]) == {m["name"] for m in want}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    return res


def _skip_odd_packages(orig):
    def collect(self, plan, pkg, out_dev):
        if pkg.seq % 2 == 0:
            orig(self, plan, pkg, out_dev)
    return collect


def _skip_other_units(orig):
    def collect(self, plan, pkg, out_dev):
        if pkg.unit == 0:
            orig(self, plan, pkg, out_dev)
    return collect


FAULTS = {"half the packages left out": ("collect", _skip_odd_packages),
          "other units' results never collected": ("collect",
                                                   _skip_other_units),
          "an answer altered where it is produced": ("dispatch", None)}


def check_planted_fault(cell: cells.Cell, fault: str, monkeypatch) -> None:
    """A run with one of ``FAULTS`` planted in the program comes out not
    correct, with a compared number over its limit."""
    from repro.core import dataplane, units

    where, wrap = FAULTS[fault]
    if where == "collect":
        for plane in (dataplane.UsmDataPlane, dataplane.BuffersDataPlane):
            monkeypatch.setattr(plane, "_collect", wrap(plane._collect))
    else:
        real = units.JaxUnit.dispatch

        def dispatch(self, fn, offset, args):
            out = real(self, fn, offset, args)
            return out.at[0].add(1.0) if offset == 0 else out
        monkeypatch.setattr(units.JaxUnit, "dispatch", dispatch)
    res = run.run_cell(cell, 21, 0.3, False)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for k, c in res["checks"].items()
               if k != "checked_launches")


def check_configuration(cell: cells.Cell, monkeypatch) -> dict:
    """Every check above on one cell; returns its traced small run."""
    check_seed_reproduces_inputs(cell)
    check_ops_do_not_depend_on_the_implementation(cell)
    check_control_fails_where_the_reference_passes(cell)
    check_small_run(cell, False)
    traced = check_small_run(cell, True)
    for fault in sorted(FAULTS):
        with monkeypatch.context() as patch:
            check_planted_fault(cell, fault, patch)
    return traced


# -- loading by name ----------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_loads_by_name(name):
    cell = cells.load(name)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer and set(cell.readers) == {
        m["name"] for m in cell.per_layer}
    assert all(callable(r) for r in cell.readers.values())
    assert cell.chips in (1, 4)
    assert cell.config["limits"] and callable(cell.ref.compare)


def test_every_metric_has_a_reader_and_every_cell_a_file():
    for m in BENCH["per_layer"]:
        assert callable(cells.reader(m["name"]))
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
    with pytest.raises(KeyError):
        cells.load("no-such-cell")


def test_a_configuration_without_a_cpu_size_names_the_missing_file(
        tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    config = BENCH["workloads"][0]["config"]
    (tmp_path / "bench" / "tests" / "small" / f"{config}.json").unlink()
    with pytest.raises(FileNotFoundError,
                       match=f"bench/tests/small/{config}.json"):
        small(BENCH["workloads"][0]["name"], tmp_path)


# -- a later configuration, from new files alone -------------------------

#: the throwaway plain reference of the Gaussian dummy
GAUSSIAN_REF = '''"""Gaussian dummy: a 5x5 binomial blur of a (rows, width) f32 image."""
import re

import numpy as np

EVENT = re.compile(r"^%_blur_kernel(\\.\\d+)? = ")
TAPS = np.array([1.0, 4.0, 6.0, 4.0, 1.0]) / 16.0


def kernel_options(cfg):
    return {}


def inputs(cfg, seed, k):
    rng = np.random.default_rng([seed & (2**64 - 1), k])
    return [rng.standard_normal((int(cfg["items"]), int(cfg["width"])),
                                dtype=np.float32)]


def reference(cfg, data, dtype="float32"):
    import jax
    import jax.numpy as jnp

    def blur(img):
        img = img.astype(dtype)
        taps = jnp.asarray(TAPS, dtype)
        rows, width = img.shape
        pad = jnp.pad(img, ((2, 2), (0, 0)))
        vert = sum(taps[d] * pad[d:d + rows] for d in range(5))
        pad = jnp.pad(vert, ((0, 0), (2, 2)))
        return sum(taps[d] * pad[:, d:d + width]
                   for d in range(5)).astype(jnp.float32)
    return np.asarray(jax.jit(blur)(data[0]))


def compare(cfg, got, want):
    if got.shape != want.shape:
        return {"max_abs_err": float("inf")}
    return {"max_abs_err": float(np.max(np.abs(got - want)))}


def ops(cfg, data, want):
    return float(cfg["ops_per_pixel"]) * want.size


def nbytes(cfg, data, want):
    return float(data[0].nbytes + want.nbytes)
'''


def _mandelbrot_dummy() -> dict:
    """A sibling of the Table-1 mandelbrot configuration, two tenants."""
    traffic = json.loads(
        (ROOT / "bench/traffic/hguided.buffers.json").read_text())
    return {
        "config": dict(json.loads(
            (ROOT / "bench/configs/mandelbrot-t1.json").read_text()),
            name="mandelbrot-dummy"),
        "small": {"items": 4096, "row_width": 64},
        "traffic": dict(traffic, tenants=["a", "b"], input_sets=2,
                        check_launches=2),
        "files": {}}


def _gaussian_dummy() -> dict:
    """The registered ``gaussian`` kernel: one (rows, width) split input
    with a 2-row halo and a trailing output width, static over two units
    (hints 0.4/0.6), BUFFERS. At 640 rows of 5120 its 384-row package
    runs as 256 + 128 pieces, each with its halo."""
    return {
        "config": {"name": "gaussian-dummy", "kernel": "gaussian_dummy",
                   "items": 5120, "width": 5120, "granularity": 128,
                   "dtype": "float32", "control_dtype": "bfloat16",
                   "ops_per_pixel": 18, "limits": {"max_abs_err": 1e-5}},
        "small": {"items": 640},
        "traffic": {"loop": "closed", "tenants": ["t0"], "input_sets": 2,
                    "warmup_launches": 1, "check_launches": 4,
                    "spec": {"units": {"count": 2, "kinds": ["cpu", "cpu"],
                                       "speed_hints": [0.4, 0.6],
                                       "dist": [0.4], "pipeline_depth": 1},
                             "scheduler": {"policy": "static"},
                             "admission": {"policy": "fifo"},
                             "memory": {"model": "buffers"}}},
        "alias": "gaussian",
        "files": {"bench/refs/gaussian_dummy.py": GAUSSIAN_REF},
        # static: 256 rows on one unit, 384 as 256 + 128 on the other;
        # each piece stages its bucket plus 4 halo rows and copies its
        # bucket back, against 640 rows in and 640 out
        "moved_ratio": ((256 + 4) * 2 + 128 + 4 + 256 * 2 + 128) / 1280}


DUMMIES = {"mandelbrot": _mandelbrot_dummy, "gaussian": _gaussian_dummy}


def _hashes(root: pathlib.Path) -> dict:
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _add(root: pathlib.Path, rel: str, text: str) -> None:
    """Write one new file of the dummy; one already there is an error."""
    path = root / rel
    assert not path.exists(), f"the dummy would overwrite {rel}"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


@pytest.mark.parametrize("dummy", sorted(DUMMIES))
def test_dummy_cell_from_new_files_alone(dummy, tmp_path, monkeypatch):
    """A later configuration is files and entries: every check of a
    configuration passes on it, and no file already there changes.

    The Gaussian dummy names the registered ``gaussian`` kernel under an
    alias of its own, so that its throwaway reference,
    ``bench/refs/<kernel>.py``, never stands where a real one may."""
    from repro.api.registry import (kernel_names, kernel_plugin,
                                    register_kernel, temporary_plugins)

    d = DUMMIES[dummy]()
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    before = _hashes(tmp_path / "bench")
    cfg, traffic = d["config"], f"dummy.{dummy}"
    cell = f"{cfg['name']}.{traffic}"
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": cfg["name"], "source": "test",
                             "file": f"bench/configs/{cfg['name']}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "chips": 1,
                               "config": cfg["name"], "traffic": traffic,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_launches", "unit": "launches",
                               "better": "higher", "source": "program_counter",
                               "layer": "admission", "moves": "items_per_s",
                               "workloads": [cell]})
    if "moved_ratio" in d:
        next(m for m in bench["per_layer"]
             if m["name"] == "moved_ratio")["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    _add(tmp_path, f"bench/configs/{cfg['name']}.json", json.dumps(cfg))
    _add(tmp_path, f"bench/tests/small/{cfg['name']}.json",
         json.dumps(d["small"]))
    _add(tmp_path, f"bench/traffic/{traffic}.json", json.dumps(d["traffic"]))
    _add(tmp_path, "bench/metrics/dummy_launches.py",
         "def read(obs):\n    return len(obs.window.served)\n")
    for rel, text in d["files"].items():
        _add(tmp_path, rel, text)
    kernel_names()  # the built-ins register before the registry is saved
    with temporary_plugins():
        if "alias" in d:
            real = kernel_plugin(d["alias"])
            register_kernel(cfg["kernel"], real.factory, fields=real.fields)
        res = check_configuration(small(cell, tmp_path), monkeypatch)
    assert res["metrics"]["dummy_launches"]["value"] == res["attempted"]
    if "moved_ratio" in d:
        assert res["metrics"]["moved_ratio"]["value"] == pytest.approx(
            d["moved_ratio"])
    assert cells.load(MANDEL, tmp_path).readers.keys() == \
        cells.load(MANDEL).readers.keys()
    assert {p: h for p, h in _hashes(tmp_path / "bench").items()
            if p in before} == before


# -- inputs ------------------------------------------------------------

@pytest.mark.parametrize("name", CELLS)
def test_seed_reproduces_inputs(name):
    check_seed_reproduces_inputs(small(name))


def test_mandelbrot_inputs_are_the_row_major_viewport_grid():
    cell = small(MANDEL)
    cfg, ref = cell.config, cell.ref
    cre, cim = ref.inputs(cfg, 3, 0)
    w = cfg["row_width"]
    assert np.array_equal(cre[:w], cre[w:2 * w])
    assert np.all(np.diff(cre[:w]) > 0) and np.all(cim[:w] == cim[0])
    assert np.all(np.diff(cim[::w]) > 0)
    assert cfg["viewport"]["re"][0] <= cre.min() < cre.max() <= \
        cfg["viewport"]["re"][1]
    counts = ref.reference(cfg, [cre, cim])
    per_package = counts.reshape(-1, cfg["granularity"]).mean(axis=1)
    # interior rows iterate many times, background rows a few
    assert per_package.max() > 10 * per_package.min()


@pytest.mark.parametrize("name", CELLS)
def test_ops_do_not_depend_on_the_implementation(name):
    check_ops_do_not_depend_on_the_implementation(small(name))


# -- peaks, trace reduction, roofline -----------------------------------

def test_peaks_are_keyed_by_device_kind_and_unknown_kind_is_an_error():
    row = run.peaks_for("TPU v5 lite")
    assert row["flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in row["source"]
    with pytest.raises(KeyError):
        run.peaks_for("cpu")


def test_union_gaps_and_labels():
    t = xplane.Trace(window=(0.0, 100.0),
                     ops={0: [("%a.1 = x", 10.0, 20.0), ("%a.1 = x", 15, 30),
                              ("%b = y", 50, 60)]},
                     host=[("H2D", 0, 12), ("D2H", 30, 45), ("outer", 30, 49),
                           ("bench.result", 0, 100)])
    assert xplane.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert t.busy_ns(0) == 30
    assert t.gaps(0) == [(0, 10), (30, 50), (60, 100)]
    assert t.kernel_ns(re.compile(r"^%a(\.\d+)? = ")) == 25
    b = t.breakdown()
    assert [n for n, _ in b["device_ops"]] == ["%a.1", "%b"]
    assert [v for _, v in b["device_ops"]] == pytest.approx([25e-9, 10e-9])
    # the longest overlap names a gap: "outer" covers 19 of (30, 50)
    assert [n for n, _ in b["idle_gaps"]] == ["no host event", "outer",
                                              "H2D"]
    assert [v for _, v in b["idle_gaps"]] == pytest.approx(
        [40e-9, 20e-9, 10e-9])


def test_roofline_share_from_ops_bytes_and_kernel_time():
    cell = small(MANDEL)
    cfg, ref = cell.config, cell.ref
    data = ref.inputs(cfg, 1, 0)
    want = ref.reference(cfg, data)
    launch = load.Launch(0, "t0", 0, cfg["items"], 0.0, 1.0)
    obs = run.Observed(
        cell=cell, window=load.Window(0.0, 1.0, [launch], []), builds=[],
        trace=xplane.Trace((0, 1e9), {0: [("%mandelbrot.1 = c", 0, 1e6)]},
                           []),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        input_sets=[data], answers={0: want})
    least = max(8 * want.sum(dtype=np.float64) / 197e12,
                12 * cfg["items"] / 819e9)
    assert cell.readers["kernel_roofline"](obs) == pytest.approx(
        100 * least / 1e-3)
    assert obs.work() == [(8 * want.sum(dtype=np.float64),
                           12 * cfg["items"])]
    assert cell.readers["kernel_ms"](obs) == pytest.approx(1.0)
    assert cell.readers["device_idle"](obs) == pytest.approx(99.9)


def test_recorded_chip_trace_reduces_as_read_by_hand():
    """The fixture is a traced run of the mandelbrot cell on one v5e."""
    from jax.profiler import ProfileData

    t = xplane.load(FIXTURE)
    data = ProfileData.from_file(str(FIXTURE))
    win = [(e.start_ns, e.start_ns + e.duration_ns) for p in data.planes
           for ln in p.lines for e in ln.events if e.name == "bench.window"]
    assert [t.window] == win
    lo, hi = win[0]
    ops = sorted((max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi),
                  e.name) for p in data.planes if p.name == "/device:TPU:0"
                 for ln in p.lines if ln.name == "XLA Ops"
                 for e in ln.events
                 if e.start_ns + e.duration_ns > lo and e.start_ns < hi)
    busy, end = 0.0, lo
    for s, e, _ in ops:
        busy += max(0.0, e - max(s, end))
        end = max(end, e)
    assert list(t.ops) == [0] and t.busy_ns(0) == pytest.approx(busy)
    kernel = sum(e - s for s, e, n in ops if n.startswith("%mandelbrot"))
    assert kernel > 0
    assert t.kernel_ns(cells.reference("mandelbrot").EVENT) == \
        pytest.approx(kernel)
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(v for _, v in b["device_ops"]) >= busy * 1e-9 * (1 - 1e-9)
    idle = hi - lo - busy
    assert sum(ns for _, ns in xplane.label_gaps(t.gaps(0), sorted(
        t.host, key=lambda h: h[1]))) == pytest.approx(idle)
    assert 0 < sum(v for _, v in b["idle_gaps"]) <= idle * 1e-9 * (1 + 1e-9)


# -- runs on the CPU ----------------------------------------------------

@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_small_run_is_correct_and_reports_the_cells_metrics(name, traced):
    check_small_run(small(name), traced)


def _cpu_env():
    import os

    return dict(os.environ, JAX_PLATFORMS="cpu")


def test_run_on_the_cpu_exits_nonzero_without_a_result():
    got = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", MANDEL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT, env=_cpu_env(),
        capture_output=True, text=True, timeout=120)
    assert got.returncode != 0 and got.stdout.strip() == ""
    assert "needs a TPU" in got.stderr


def test_run_without_the_program_exits_nonzero_without_a_result(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    got = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", MANDEL, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=_cpu_env(),
        capture_output=True, text=True, timeout=120)
    assert got.returncode != 0 and got.stdout.strip() == ""


# -- the comparison's control and planted faults -------------------------

@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_reference_passes(name):
    check_control_fails_where_the_reference_passes(small(name))


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_a_planted_fault_makes_the_run_incorrect(name, fault, monkeypatch):
    check_planted_fault(small(name), fault, monkeypatch)
