"""The Pallas kernels compile for a TPU v5e at the widths they serve.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: tilings that break the (8, 128) block rule, loop
carries Mosaic cannot lay out, blocks that overflow VMEM. These tests
compile each kernel for one chip of a described ``v5e:2x2`` topology —
nothing runs, so no chip is needed — and check that the program really
holds the Mosaic kernel (``tpu_custom_call``).

The six engine kernels are compiled at the package buckets the data
plane presents at the paper's Table-1 sizes (``repro.core.workloads.
SPECS``): the smallest bucket (Table 1's local work size) and the
largest (the whole launch, rounded up to a power of two). Widths are
lane-aligned: gaussian and matmul are square at about the Table-1 item
count, rap rows have the registered width 48.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file. All cases live in this one file so that they share that process.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ENGINE_KERNELS = ("gaussian", "mandelbrot", "matmul", "rap", "ray",
                  "taylor")
RAP_WIDTH = 48


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described chip's programs cannot be read back from the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _engine_case(name: str, bucket: str):
    """The Pallas wrapper and argument shapes of one package bucket."""
    from repro.core.dataplane import _bucket
    from repro.core.workloads import SPECS
    from repro.kernels import (gaussian_blur_halo, mandelbrot, matmul, rap,
                               raytrace, taylor_sin)

    spec = SPECS[name]
    side = 128 * round(math.sqrt(spec.work_items) / 128)
    total = side if name in ("gaussian", "matmul") else spec.work_items
    rows = _bucket(spec.local_work_size if bucket == "smallest" else total)
    f32 = jnp.float32
    cases = {
        "gaussian": (lambda x: gaussian_blur_halo(x, interpret=False),
                     [((rows + 4, side), f32)]),
        "mandelbrot": (lambda a, b: mandelbrot(a, b, interpret=False),
                       [((rows,), f32)] * 2),
        "matmul": (lambda a, b: matmul(a, b, interpret=False),
                   [((rows, side), f32), ((side, side), f32)]),
        "rap": (lambda v, n: rap(v, n, interpret=False),
                [((rows, RAP_WIDTH), f32), ((rows,), jnp.int32)]),
        "ray": (lambda x, y, z, s: raytrace(x, y, z, s, interpret=False),
                [((rows,), f32)] * 3 + [((8, 5), f32)]),
        "taylor": (lambda x: taylor_sin(x, interpret=False),
                   [((rows,), f32)]),
    }
    return cases[name]


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("bucket", ("smallest", "largest"))
@pytest.mark.parametrize("name", ENGINE_KERNELS)
def test_engine_kernel_compiles_for_v5e(name, bucket, one_chip):
    fn, shapes = _engine_case(name, bucket)
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_matmul_compiles_for_v5e_in_bf16(one_chip):
    from repro.kernels import matmul

    bf16 = jnp.bfloat16
    compiled = _compile(lambda a, b: matmul(a, b, interpret=False),
                        [((1024, 4864), bf16), ((4864, 4864), bf16)],
                        one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    from repro.kernels import flash_attention

    bf16 = jnp.bfloat16
    q, kv = ((1, 16, 2048, 128), bf16), ((1, 8, 2048, 128), bf16)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        [q, kv, kv], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_linear_attention_compiles_for_v5e(one_chip):
    from repro.kernels import linear_attention

    f32 = jnp.float32
    qkv, decay = ((16, 2048, 128), f32), ((16, 2048), f32)
    compiled = _compile(
        lambda q, k, v, ld: linear_attention(q, k, v, ld, interpret=False),
        [qkv, qkv, qkv, decay], one_chip)
    assert "tpu_custom_call" in compiled.as_text()
