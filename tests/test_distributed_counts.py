"""Count-aggregated distributed engine (the §Perf Lemma-1-on-the-wire
optimization): correctness vs power iteration, payload-flatness in K,
packed-lane exactness. Runs in a subprocess with 8 forced host devices."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_count_engine_correct_and_flat_payload():
    r = _run(textwrap.dedent("""
        import json, jax, numpy as np
        from repro.core import power_iteration, l1_error, normalized
        from repro.core.distributed_counts import distributed_pagerank_counts
        from repro.graphs import erdos_renyi
        g = erdos_renyi(200, 6.0, seed=3)
        pi_ref, _, _ = power_iteration(g, 0.2)
        out = {}
        for K in (50, 200):
            res = distributed_pagerank_counts(g, 0.2, K, jax.random.PRNGKey(1))
            out[str(K)] = dict(
                a2a=res.a2a_bytes_total, overflow=res.overflow,
                l1=l1_error(normalized(res.pi), pi_ref),
                zeta=int(res.zeta.sum()), rounds=res.rounds)
        print(json.dumps(out))
    """))
    for K in ("50", "200"):
        assert r[K]["overflow"] == 0
        assert r[K]["l1"] < 0.12
        expected = 200 * int(K) / 0.2
        assert abs(r[K]["zeta"] - expected) / expected < 0.06
    # Lemma-1 wire: 4x the walks costs < 1.6x the bytes (vs 4x for
    # per-walk routing)
    assert r["200"]["a2a"] < 1.6 * r["50"]["a2a"], (r["50"], r["200"])


def test_packed_lanes_exact():
    r = _run(textwrap.dedent("""
        import json, jax, numpy as np
        from repro.core.distributed_counts import distributed_pagerank_counts
        from repro.graphs import barabasi_albert
        g = barabasi_albert(120, 3, seed=1)
        a = distributed_pagerank_counts(g, 0.25, 80, jax.random.PRNGKey(2),
                                        packed=False)
        b = distributed_pagerank_counts(g, 0.25, 80, jax.random.PRNGKey(2),
                                        packed=True)
        print(json.dumps(dict(
            equal=bool(np.array_equal(np.asarray(a.zeta), np.asarray(b.zeta))),
            ratio=a.a2a_bytes_total / max(b.a2a_bytes_total, 1))))
    """))
    assert r["equal"] is True            # packing is bit-exact
    assert 1.9 < r["ratio"] < 2.1        # exactly half the wire bytes


@pytest.mark.parametrize("packed,n_loc,walks,want", [
    (None, 1 << 16, 2 * 32767, True),
    (None, (1 << 16) + 1, 100, False),     # local id past 16 bits
    (None, 100, 2 * 32767 + 1, False),     # a count could spill twice
    (False, 100, 100, False),
    (True, 100, 100, True),
])
def test_resolve_packed_packs_only_where_exact(packed, n_loc, walks, want):
    from repro.core.distributed_counts import resolve_packed
    assert resolve_packed(packed, n_loc, walks) is want


def test_resolve_packed_refuses_inexact_packing():
    from repro.core.distributed_counts import resolve_packed
    with pytest.raises(ValueError, match="packed count lanes"):
        resolve_packed(True, 1 << 17, 100)


# --- the exchange's sorted segment reduction ---------------------------

def _funnel(n):
    """Every vertex but 0 links to 0, and 0 and 1 link to a few more in
    one degree bucket (so it holds padding slots): vertex 0 receives all
    but seven real slots, over a third of all slots at any shard count."""
    import numpy as np
    from repro.core.graph import from_edges
    src = np.concatenate([np.arange(1, n), [0, 0, 0, 1, 1, 1]])
    dst = np.concatenate([np.zeros(n - 1, np.int64), [1, 2, 3, 2, 3, 4]])
    return from_edges(src, dst, n)


def _graph(name):
    from repro.graphs import barabasi_albert_hub
    return _funnel(61) if name == "funnel" else barabasi_albert_hub(61, 3,
                                                                    seed=4)


@pytest.mark.parametrize("shards", [1, 2, 4])
@pytest.mark.parametrize("graph", ["hub", "funnel"])
def test_sorted_segment_sums_equal_segment_sum(shards, graph):
    """Every shard's per-vertex sums by sort + cumsum + take equal a
    scatter-add over the same keys, padding slots (keyed n_pad, here
    given nonzero values) counting for no vertex."""
    import jax
    import numpy as np
    from repro.core.distributed_counts import shard_graph_padded
    from repro.core.routing import sorted_segment_sums
    g = _graph(graph)
    sg = shard_graph_padded(g, shards)
    pad = sg.bnbr == sg.n_pad
    assert pad.any()
    if shards > 1:   # 61 vertices: rows made entirely of padding
        assert (sg.bperm == -1).any()
    if graph == "funnel":
        assert (sg.bnbr == 0).sum() * 3 > sg.bnbr.size
    rng = np.random.default_rng(shards)
    vals = rng.integers(0, 50, sg.bnbr.shape).astype(np.int32)
    reduce = jax.jit(sorted_segment_sums)
    for p in range(shards):
        got = np.asarray(reduce(sg.bnbr[p], sg.bends[p], vals[p]))
        want = np.asarray(jax.ops.segment_sum(vals[p], sg.bnbr[p],
                                              num_segments=sg.n_pad))
        assert np.array_equal(got, want), p
        assert got.sum() == vals[p][~pad[p]].sum()


def test_zeta_is_bit_identical_to_a_scatter_add_exchange():
    """The sorted reduction changes no draw and no sum: on a tiny
    Graph500 graph, at 1 and 2 shards, `zeta` equals that of the same
    engine whose exchange sums by `segment_sum` (the scatter-add it
    replaced), and the two shard counts agree with each other."""
    r = _run(textwrap.dedent("""
        import json, os, sys, jax, numpy as np
        from jax.sharding import Mesh
        sys.path.insert(0, os.path.join(%r, ".."))
        from bench.data.graph500 import graph500
        from repro.core import distributed_counts as dc
        from repro.core.graph import CSRGraph

        def segment_sum_reference(dst, ends, values):
            return jax.ops.segment_sum(values, dst,
                                       num_segments=ends.shape[0] - 1)

        row_ptr, col_idx, out_deg = graph500(8, 22)
        g = CSRGraph(row_ptr=row_ptr, col_idx=col_idx, out_deg=out_deg,
                     n=len(out_deg), m=len(col_idx), undirected=True)
        zetas, out = {}, {}
        for shards in (1, 2):
            mesh = Mesh(np.array(jax.devices()[:shards]), (dc.AXIS,))
            for name in ("sorted", "scatter"):
                real = dc.sorted_segment_sums
                if name == "scatter":
                    dc.sorted_segment_sums = segment_sum_reference
                dc.make_count_superstep.cache_clear()
                try:
                    res = dc.distributed_pagerank_counts(
                        g, 0.15, 8, jax.random.PRNGKey(5), mesh=mesh)
                finally:
                    dc.sorted_segment_sums = real
                    dc.make_count_superstep.cache_clear()
                zetas[shards, name] = np.asarray(res.zeta)
                out[f"{shards}.{name}.rounds"] = res.rounds
        ref = zetas[1, "scatter"]
        out["equal"] = {f"{s}.{n}": bool(np.array_equal(z, ref))
                        for (s, n), z in zetas.items()}
        out["visits"] = int(ref.sum())
        print(json.dumps(out))
    """ % REPO_SRC), devices=2)
    assert all(r["equal"].values()), r
    assert r["visits"] > 0
    assert len({v for k, v in r.items() if k.endswith("rounds")}) == 1, r


def test_one_shard_exchange_has_no_scatter():
    """The one-shard exchange sums the slots by a sort, a cumsum and a
    take at the segment ends: its lowered program holds a sort and no
    scatter."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed_counts import AXIS, audit_spec
    mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))
    spec = audit_spec(_graph("hub"), mesh)
    (prog,) = [p for p in spec.programs if p.program == "exchange"]
    text = prog.fn.lower(*prog.example_args).as_text()
    assert "sort" in text
    assert "scatter" not in text


def test_build_counts_the_exchange_sort_entries():
    """`counts.build` counts the slots the exchange sorts each round: the
    bucketed layout's `total_edges`, padding slots included."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed_counts import (AXIS,
                                               distributed_pagerank_counts,
                                               shard_graph_padded)
    from repro.runtime import tracing
    g = _graph("hub")
    mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))
    tracing.clear()
    distributed_pagerank_counts(g, 0.3, 4, jax.random.PRNGKey(4), mesh=mesh)
    (build,) = [r for r in tracing.spans() if r.name == "counts.build"]
    total = shard_graph_padded(g, 1).layout.total_edges
    assert build.counts["exchange_sort_entries"] == total > g.m


@pytest.mark.parametrize("walks,refused", [((1 << 31) - 1, False),
                                           (1 << 31, True)])
def test_check_sum_exact_bounds_the_walks(walks, refused):
    from repro.core.distributed_counts import check_sum_exact
    if refused:
        with pytest.raises(ValueError, match="n \\* walks < 2\\^31"):
            check_sum_exact(walks)
    else:
        check_sum_exact(walks)


def test_a_job_that_could_wrap_the_int32_sum_is_refused_at_build():
    """n * walks = 2^31 could wrap the exchange's int32 cumsum: the job
    is refused in its build, before any round runs."""
    import jax
    from repro.core.distributed_counts import distributed_pagerank_counts
    from repro.graphs import ring
    from repro.runtime import tracing
    tracing.clear()
    with pytest.raises(ValueError, match="n \\* walks < 2\\^31"):
        distributed_pagerank_counts(ring(4), 0.2, 1 << 29,
                                    jax.random.PRNGKey(0))
    assert not [r for r in tracing.spans() if r.name == "round.counts"]


COUNTS_LAYOUT_PARITY_CODE = """
import json
import jax, numpy as np
from repro.graphs import barabasi_albert_hub
from repro.core.distributed_counts import distributed_pagerank_counts

g = barabasi_albert_hub(96, 3, seed=4)
a = distributed_pagerank_counts(g, 0.2, 100, jax.random.PRNGKey(3))
rc = distributed_pagerank_counts(g, 0.2, 100, jax.random.PRNGKey(3),
                                 bucketed=False)
print(json.dumps(dict(
    layout_equal=bool(np.array_equal(np.asarray(a.zeta),
                                     np.asarray(rc.zeta))),
    rounds=[a.rounds, rc.rounds],
    residual=[a.residual, rc.residual],
    overflow=[a.overflow, rc.overflow])))
"""


def test_counts_engine_pallas_and_layout_bit_parity():
    """The count engine's draws are a pure function of (key, row id,
    slot): the bucketed and the flat single-bucket layout must give
    bit-identical trajectories on the hub fixture."""
    from conftest import run_forced_devices
    r = run_forced_devices(COUNTS_LAYOUT_PARITY_CODE)
    assert r["layout_equal"]
    assert len(set(r["rounds"])) == 1
    assert r["residual"] == [0, 0]
    assert r["overflow"] == [0, 0]


@pytest.mark.parametrize("entry", ["counts", "improved"])
def test_use_pallas_is_refused(entry):
    """The two entry points the benchmark calls keep a `use_pallas`
    keyword only to refuse a true value: there is no kernel path."""
    import jax
    from repro.core.distributed_counts import distributed_pagerank_counts
    from repro.core.distributed_improved import distributed_improved_pagerank
    from repro.graphs import ring
    run = dict(counts=distributed_pagerank_counts,
               improved=distributed_improved_pagerank)[entry]
    with pytest.raises(ValueError, match="use_pallas"):
        run(ring(8), 0.2, 2, jax.random.PRNGKey(0), use_pallas=True)
