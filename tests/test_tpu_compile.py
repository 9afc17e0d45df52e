"""Compile the cells' stage programs for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described, unattached
v5e topology, and refuses what the chip's compiler would refuse
(unsupported ops, too much memory). The programs are the XLA programs
the benchmark's cells run, built by the engines' own step makers (the
CONGEST audit specs) over a mesh of described chips: the counts
engine's `jit_sample` at bucket widths 1 to 14 and its `jit_exchange`,
on one chip and on the 2x2 host's four; Algorithm 2's Phase-1, stitch
index, stitch and count programs on one chip. The graphs are small, so
the file stays quick; full-size compiles are for a scratch script before
a chip run.

The topology is described inside a module fixture, never while a module
is imported, so every pytest worker collects the same tests and only the
worker running this file loads the TPU library.
"""
import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import distributed_counts, distributed_improved
from repro.core.distributed import AXIS
from repro.core.graph import from_edges
from repro.graphs import erdos_renyi

EPS = 0.15
WALKS_PER_NODE = 32
N = 1 << 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def meshes(topo):
    """Meshes of one and of four described chips, with the persistent
    compile cache off: entries compiled for an unattached chip cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield {k: Mesh(np.array(topo.devices[:k]), (AXIS,)) for k in (1, 4)}
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def graph():
    return erdos_renyi(N, 8.0, seed=1)


def _graph_of_width(width: int):
    """A directed graph on N vertices whose out-degrees run 0..width, so
    the sampler's buckets are those of a graph with max degree `width`."""
    rng = np.random.default_rng(width)
    deg = np.arange(N) % (width + 1)
    src = np.repeat(np.arange(N), deg)
    dst = (src + 1 + rng.integers(0, N - 1, size=len(src))) % N
    return from_edges(src, dst, N, dedup=False)


def _compile(mesh, prog):
    """Compile `prog` with its example arguments placed on `mesh`: the
    per-shard arrays split over the shard axis, scalars replicated."""
    shards = mesh.devices.size

    def place(x):
        split = x.ndim > 0 and x.shape[0] == shards
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, P(AXIS) if split else P()))

    args = jax.tree_util.tree_map(place, prog.example_args)
    text = prog.fn.lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' not in text
    return text


def _counts_program(mesh, graph, name):
    spec = distributed_counts.audit_spec(graph, mesh, eps=EPS,
                                         walks_per_node=WALKS_PER_NODE)
    (prog,) = [p for p in spec.programs if p.program == name]
    return prog


@pytest.mark.parametrize("width", [1, 2, 4, 8, 14])
def test_sample_compiles(meshes, width):
    g = _graph_of_width(width)
    assert g.max_out_deg == width
    prog = _counts_program(meshes[1], g, "sample")
    assert prog.fn.__name__ == "sample"          # jit_sample in a trace
    _compile(meshes[1], prog)


def test_exchange_compiles(meshes, graph):
    prog = _counts_program(meshes[1], graph, "exchange")
    assert prog.fn.__name__ == "exchange"
    text = _compile(meshes[1], prog)
    assert "all-to-all" not in text               # one shard: no wire


def test_exchange_compiles_on_four_chips(meshes, graph):
    text = _compile(meshes[4],
                    _counts_program(meshes[4], graph, "exchange"))
    assert "all-to-all" in text


@pytest.mark.parametrize("stage,name,jitted", [
    ("phase1", "request", "p1_request"),
    ("phase1", "sample", "p1_sample"),
    ("phase1", "assign", "p1_assign"),
    ("phase2", "index", "p2_index"),
    ("phase2", "stitch", "p2_stitch"),
    ("phase3", "count", "p3_count"),
])
def test_improved_stage_compiles(meshes, graph, stage, name, jitted):
    spec = distributed_improved.audit_spec(graph, meshes[1], eps=EPS,
                                           walks_per_node=WALKS_PER_NODE)
    (prog,) = [p for p in spec.programs
               if (p.stage, p.program) == (stage, name)]
    assert prog.fn.__name__ == jitted
    _compile(meshes[1], prog)
