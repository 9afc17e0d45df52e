"""Compile the Pallas kernels for a TPU v5e chip at main-path shapes.

No chip is needed: the TPU compiler compiles for a described, unattached
v5e topology, and refuses what the chip's compiler would refuse
(unaligned blocks, unsupported gathers, too much VMEM). The shapes are
those of the one-chip smoke run (`chip_smoke.py`): a `directed_web`
graph with n = 2^22 vertices and about 16.6M edges, whose counts-engine
buckets hold up to ~2.2M rows at widths 1..14.

The topology is described inside a module fixture, never while a module
is imported, so every pytest worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.histogram.histogram import histogram_pallas
from repro.kernels.multinomial_rows.multinomial_rows import \
    multinomial_rows_pallas
from repro.kernels.segment_spmv.segment_spmv import segment_spmv_pallas
from repro.kernels.walk_step import walk_step
from repro.kernels.walk_step.walk_step import walk_step_pallas

N = 1 << 22            # vertices of the smoke run's graph
M = 16_595_258         # its edges
BUCKET_ROWS = 2_177_084   # rows of its largest degree bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shape factory on one described chip, with the persistent compile
    cache off: entries compiled for an unattached chip cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    return compiled


@pytest.mark.parametrize("width", [1, 2, 4, 8, 14])
def test_multinomial_rows_compiles(one_chip, width):
    rows = one_chip((BUCKET_ROWS,))
    _compile(functools.partial(multinomial_rows_pallas, eps=0.2, width=width,
                               interpret=False),
             rows, rows, rows, one_chip((2,), jnp.uint32))


def test_histogram_compiles(one_chip):
    _compile(functools.partial(histogram_pallas, num_segments=N,
                               interpret=False), one_chip((1 << 21,)))


def test_segment_spmv_compiles(one_chip):
    _compile(functools.partial(segment_spmv_pallas, num_segments=N,
                               interpret=False),
             one_chip((M,), jnp.float32), one_chip((M,)))


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: Only 2D gather is supported")
def test_walk_step_compiles(one_chip):
    walks = one_chip((1 << 18,))
    u = one_chip((1 << 18,), jnp.float32)
    try:
        _compile(functools.partial(walk_step_pallas, eps=0.2,
                                   interpret=False),
                 walks, walks, u, u, one_chip((N + 1,)), one_chip((M,)),
                 one_chip((N,)))
    except NotImplementedError as e:
        # any other refusal is a new failure, not this expected one
        if "Only 2D gather is supported" not in str(e):
            raise AssertionError(f"walk_step refused for another reason: "
                                 f"{e}") from e
        raise


def test_walk_step_refuses_compiled_call():
    """Off interpret mode the public wrapper raises instead of falling
    back to interpret mode or to the jnp path."""
    x = jnp.zeros((8,), jnp.int32)
    with pytest.raises(NotImplementedError, match="does not compile for TPU"):
        walk_step(x, x, x.astype(jnp.float32), x.astype(jnp.float32),
                  jnp.zeros((3,), jnp.int32), x, x[:2], eps=0.2,
                  interpret=False)
