"""Elastic-runtime coverage: mesh-size-agnostic snapshots and resume on a
resized mesh.

In-process units exercise the schema-driven repartitioner jax-free(ish):
bit-exact P -> P' -> P round trips for every layout kind, canonical walk
packing, the coupon-slot bijection against a freshly built pool layout,
auto-growing walk caps under skew, and the collision-resistant per-shard
key re-derivation. The Supervisor's mismatch detection / relayout routing /
re-anchor save is unit-tested on toy host state.

The engine-level guarantees run in subprocesses (XLA's device count is
process-global): a run killed on an 8-shard mesh resumes on {1, 2, 4}
shards bit-exactly for the count-state engine (counter-based per-vertex
RNG + replicated round key), bit-exactly for the 3-phase engine when the
kill lands in the RNG-free Phase 2, and tolerance-gated for the directed
engine when per-shard key streams must be re-derived. A second forced-16
subprocess covers growing the mesh (8 -> 16). The resident PPR service is
resized mid-traffic without dropping cached or in-flight queries.
"""
import textwrap

import numpy as np
import pytest

from conftest import run_forced_devices

from repro.checkpoint import (LayoutSpec, derive_shard_keys, pack_json,
                              relayout_arrays, relayout_pagerank_state,
                              relayout_staged_flat)
from repro.checkpoint.elastic import _slot_index


# ---------------------------------------------------------------------------
# in-process units: the schema-driven repartitioner
# ---------------------------------------------------------------------------

def _shard_vertex(base: np.ndarray, n: int, shards: int) -> np.ndarray:
    n_loc = -(-n // shards)
    out = np.zeros((n_loc * shards,) + base.shape[1:], dtype=base.dtype)
    out[:n] = base
    return out.reshape((shards, n_loc) + base.shape[1:])


@pytest.mark.parametrize("p_mid", [1, 3, 16])
def test_vertex_roundtrip_bit_exact(p_mid):
    """vertex buffers re-split along the contiguous partition and round-
    trip 8 -> P' -> 8 bit-exactly, including a trailing feature axis."""
    n = 37
    rng = np.random.default_rng(0)
    base = rng.integers(0, 1000, size=(n, 2)).astype(np.int32)
    spec = dict(z=LayoutSpec(kind="vertex", n=n))
    a8 = _shard_vertex(base, n, 8)
    mid = relayout_arrays(dict(z=a8), spec, 8, p_mid)["z"]
    np.testing.assert_array_equal(mid, _shard_vertex(base, n, p_mid))
    back = relayout_arrays(dict(z=mid), spec, p_mid, 8)["z"]
    np.testing.assert_array_equal(back, a8)


def _walk_multiset(pos, qid=None):
    live = pos.reshape(-1) >= 0
    v = pos.reshape(-1)[live].tolist()
    if qid is None:
        return sorted(v)
    return sorted(zip(v, qid.reshape(-1)[live].tolist()))


@pytest.mark.parametrize("p_mid", [1, 3, 16])
def test_walk_roundtrip_canonical_with_aux(p_mid):
    """Walk lanes (+ their aux lane) preserve the walk multiset under any
    re-bucketing, and with a pinned cap the canonical sorted packing makes
    P -> P' -> P bit-exact."""
    n, cap, P = 50, 24, 8
    rng = np.random.default_rng(1)
    pos = np.full((P, cap), -1, np.int32)
    qid = np.zeros((P, cap), np.int32)
    for _ in range(70):     # scattered, duplicated, unsorted live walks
        p, s = rng.integers(P), rng.integers(cap)
        pos[p, s] = rng.integers(n)
        qid[p, s] = rng.integers(4)
    specs = dict(pos=LayoutSpec(kind="walk", n=n, cap=cap, fill=-1,
                                aux=("qid",)),
                 qid=LayoutSpec(kind="walk_aux", fill=0))
    mid = relayout_arrays(dict(pos=pos, qid=qid), specs, P, p_mid)
    assert _walk_multiset(mid["pos"], mid["qid"]) == \
        _walk_multiset(pos, qid)
    # canonical: re-laying-out an already-canonical layout is the identity
    again = relayout_arrays(mid, specs, p_mid, p_mid)
    np.testing.assert_array_equal(again["pos"], mid["pos"])
    np.testing.assert_array_equal(again["qid"], mid["qid"])
    # round trip lands on the CANONICAL 8-shard packing of the original
    back = relayout_arrays(mid, specs, p_mid, P)
    canon = relayout_arrays(dict(pos=pos, qid=qid), specs, P, P)
    np.testing.assert_array_equal(back["pos"], canon["pos"])
    np.testing.assert_array_equal(back["qid"], canon["qid"])


def test_walk_cap_autogrows_under_skew():
    """Every walk on one vertex: the declared cap cannot hold shard 0's
    bucket, so relayout grows it instead of failing the resume."""
    n = 64
    host = dict(
        pos=np.zeros((2, 32), np.int32),          # 64 walks, all at vertex 0
        zeta=np.zeros((2, 32), np.int32),
        key=np.arange(4, dtype=np.uint32).reshape(2, 2),
        round=np.int32(3), dropped=np.int32(0), waited=np.int32(0))
    out = relayout_pagerank_state(host, n, 8, cap=4)
    assert out["pos"].shape[0] == 8
    assert out["pos"].shape[1] >= 64          # grew past the declared 4
    assert _walk_multiset(out["pos"]) == [0] * 64
    assert out["zeta"].shape == (8, 8)
    assert out["key"].shape == (8, 2)


def test_slot_bijection_matches_fresh_pool_layout():
    """A coupon-slot buffer re-homed 8 -> 3 is bit-identical to the layout
    a fresh 3-shard engine would build, and round-trips bit-exactly."""
    n = 29
    rng = np.random.default_rng(2)
    pool = rng.integers(0, 5, size=n).astype(np.int64)
    total = int(pool.sum())

    def build(shards):
        idx, S = _slot_index(pool, n, shards)
        buf = np.full(shards * S, -1, np.int64)
        buf[idx] = np.arange(total)     # coupon (v, j), vertex-major
        return buf.reshape(shards, S)

    spec = dict(b=LayoutSpec(kind="slot", n=n, pool=pool, fill=-1))
    b8 = build(8)
    got3 = relayout_arrays(dict(b=b8), spec, 8, 3)["b"]
    np.testing.assert_array_equal(got3, build(3))
    back = relayout_arrays(dict(b=got3), spec, 3, 8)["b"]
    np.testing.assert_array_equal(back, b8)
    # a buffer that does not match the claimed old layout is an error
    with pytest.raises(ValueError, match="does not match"):
        relayout_arrays(dict(b=b8), spec, 4, 3)


def test_derive_shard_keys_separates_permuted_layouts():
    """Row-permuted old key arrays must derive DIFFERENT new streams (the
    old XOR-reduce aliased them), and the derivation is deterministic."""
    a = np.arange(16, dtype=np.uint32).reshape(8, 2)
    b = a[::-1].copy()
    # XOR cannot tell these apart — the hash-based derivation must
    assert np.array_equal(np.bitwise_xor.reduce(a.reshape(-1)),
                          np.bitwise_xor.reduce(b.reshape(-1)))
    ka, kb = derive_shard_keys(a, 4), derive_shard_keys(b, 4)
    assert ka.shape == (4, 2)
    assert not np.array_equal(ka, kb)
    np.testing.assert_array_equal(ka, derive_shard_keys(a, 4))
    # distinct shards get distinct keys
    assert len({tuple(row) for row in ka.tolist()}) == 4


def test_relayout_schema_errors():
    arr = np.zeros((2, 4), np.int32)
    with pytest.raises(ValueError, match="no layout schema"):
        relayout_arrays(dict(x=arr), {}, 2, 4)
    with pytest.raises(ValueError, match="unknown layout kind"):
        relayout_arrays(dict(x=arr), dict(x=LayoutSpec(kind="bogus")), 2, 4)
    flat = dict(stage=pack_json("phase9"), host=pack_json({}))
    with pytest.raises(ValueError, match="no layout schema declared"):
        relayout_staged_flat(flat, 2, 4, dict(phase1={}))


def test_relayout_staged_flat_uses_stage_schema():
    """The flat snapshot's stage tag selects the spec map; non-array leaves
    (stage, host accumulators) pass through untouched."""
    n = 6
    base = np.arange(n, dtype=np.int32)
    flat = {"stage": pack_json("count"),
            "host": pack_json(dict(rounds=7)),
            "arrays/z": _shard_vertex(base, n, 8)}
    layouts = dict(count=dict(z=LayoutSpec(kind="vertex", n=n)))
    out = relayout_staged_flat(flat, 8, 2, layouts)
    np.testing.assert_array_equal(out["stage"], flat["stage"])
    np.testing.assert_array_equal(out["host"], flat["host"])
    np.testing.assert_array_equal(out["arrays/z"],
                                  _shard_vertex(base, n, 2))


# ---------------------------------------------------------------------------
# in-process units: Supervisor mismatch detection + re-anchor (jax-free)
# ---------------------------------------------------------------------------

def _toy_supervisor(ck, meta_shards, relayout=None, checkpoint_every=100):
    from repro.runtime import Supervisor

    def step(s):
        s = dict(s, count=int(s["count"]) + 1)
        return s, s["count"] >= 6

    return Supervisor(
        step,
        lambda s: dict(x=np.asarray(s["x"]),
                       count=np.asarray(s["count"])),
        lambda f: dict(x=np.asarray(f["x"]),
                       count=int(np.asarray(f["count"]))),
        ck, checkpoint_every=checkpoint_every,
        meta_fn=lambda: dict(shards=meta_shards), relayout=relayout)


def test_supervisor_shard_mismatch_without_hook_raises(tmp_path):
    from repro.checkpoint import Checkpointer
    ck = Checkpointer(str(tmp_path))
    ck.save(3, dict(x=np.ones(8), count=np.asarray(3)),
            metadata=dict(shards=8))
    sup = _toy_supervisor(ck, meta_shards=4)
    with pytest.raises(ValueError, match="no relayout hook"):
        sup.run(None, resume=True)


def test_supervisor_routes_resume_through_relayout_and_reanchors(tmp_path):
    """Manifest shards != live shards: the restored flat dict goes through
    the relayout hook, and the supervisor immediately re-snapshots the
    NEW-mesh state at the same step so a later crash recovers it."""
    from repro.checkpoint import Checkpointer
    ck = Checkpointer(str(tmp_path))
    ck.save(3, dict(x=np.arange(8, dtype=np.int64), count=np.asarray(3)),
            metadata=dict(shards=8))
    seen = []

    def relayout(flat, old_shards):
        seen.append(old_shards)
        return dict(flat, x=np.asarray(flat["x"]).reshape(4, 2).sum(1))

    sup = _toy_supervisor(ck, meta_shards=4, relayout=relayout)
    res = sup.run(None, resume=True)
    assert seen == [8]
    assert res.restarts == 0 and res.state["count"] == 6
    np.testing.assert_array_equal(res.state["x"], [1, 5, 9, 13])
    # the re-anchor happened at the resumed step, under the NEW mesh size
    flat, manifest = ck.restore()
    assert manifest["metadata"] == dict(shards=4)
    # ...and the final-state snapshot (done-save) is the latest step
    assert manifest["step"] == 6


def test_supervisor_matching_shards_skips_relayout(tmp_path):
    from repro.checkpoint import Checkpointer
    ck = Checkpointer(str(tmp_path))
    ck.save(3, dict(x=np.ones(8), count=np.asarray(3)),
            metadata=dict(shards=8))

    def boom(flat, old):           # must not be consulted on a same-size mesh
        raise AssertionError("relayout called despite matching shards")

    res = _toy_supervisor(ck, meta_shards=8, relayout=boom).run(
        None, resume=True)
    assert res.state["count"] == 6


def test_final_snapshot_written_on_done(tmp_path):
    """A run finishing BETWEEN periodic checkpoints still leaves the
    directory holding its final state (satellite: done-save)."""
    from repro.checkpoint import Checkpointer
    from repro.runtime import Stage, StagedState, StageSchedule, run_staged

    def step(ms):
        ms.host["count"] += 1
        return ms, ms.host["count"] >= 5

    sched = StageSchedule([Stage("s", step)])
    ms = StagedState(stage="s", arrays={}, host=dict(count=0))
    out, restarts, ckpts = run_staged(
        sched, ms, lambda n, a: a, checkpoint_dir=str(tmp_path),
        checkpoint_every=100)
    assert (restarts, ckpts) == (0, 2)      # round-0 anchor + done-save
    from repro.runtime import staged_from_host
    flat, manifest = Checkpointer(str(tmp_path)).restore()
    assert manifest["step"] == 5
    assert staged_from_host(flat, lambda n, a: a).host == dict(count=5)


def test_run_staged_elastic_resume_jax_free(tmp_path):
    """End-to-end through run_staged on toy state: kill at 8 shards,
    resume at 4 — the snapshot re-layouts through the declared schema and
    the manifest re-anchors to the live mesh size."""
    from repro.checkpoint import Checkpointer
    from repro.runtime import (SimulatedFailure, Stage, StagedState,
                               StageSchedule, run_staged)

    n = 6
    base = np.arange(n, dtype=np.int32)
    layouts = dict(s=dict(x=LayoutSpec(kind="vertex", n=n)))

    def step(ms):
        ms.host["count"] += 1
        return ms, ms.host["count"] >= 4

    sched = StageSchedule([Stage("s", step)])
    d = str(tmp_path)
    st8 = StagedState(stage="s", arrays=dict(x=_shard_vertex(base, n, 8)),
                      host=dict(count=0), layouts=layouts, shards=8)
    with pytest.raises(SimulatedFailure):
        run_staged(sched, st8, lambda name, a: a, checkpoint_dir=d,
                   fail_at=[2], checkpoint_every=2, max_restarts=0)
    st4 = StagedState(stage="s", arrays=dict(x=_shard_vertex(base, n, 4)),
                      host=dict(count=0), layouts=layouts, shards=4)
    out, restarts, _ = run_staged(sched, st4, lambda name, a: a,
                                  checkpoint_dir=d, resume=True,
                                  checkpoint_every=100)
    assert restarts == 0 and out.host["count"] == 4
    np.testing.assert_array_equal(out.arrays["x"],
                                  _shard_vertex(base, n, 4))
    assert Checkpointer(d).restore()[1]["metadata"] == dict(shards=4)


# ---------------------------------------------------------------------------
# engine-level elastic resume (subprocess: device count is process-global)
# ---------------------------------------------------------------------------

ELASTIC_CODE = textwrap.dedent("""
    import json, shutil, tempfile
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import l1_error, normalized, power_iteration, topk_overlap
    from repro.core.distributed import AXIS
    from repro.core.distributed_counts import distributed_pagerank_counts
    from repro.core.distributed_directed import distributed_directed_pagerank
    from repro.core.distributed_improved import distributed_improved_pagerank
    from repro.graphs import directed_web, erdos_renyi
    from repro.runtime import SimulatedFailure

    devs = jax.devices()
    def submesh(p):
        return Mesh(np.array(devs[:p]), (AXIS,))

    def flat_zeta(r, n):
        return np.asarray(r.zeta).reshape(-1)[:n]

    def kill(engine, g, K, key, d, fail_at, **kw):
        died = False
        try:
            engine(g, 0.25, K, key, checkpoint_dir=d, fail_at=fail_at,
                   checkpoint_every=2, max_restarts=0, **kw)
        except SimulatedFailure:
            died = True
        return died

    out = {}

    # counts: replicated round key + counter-based per-vertex draws make
    # the trajectory a pure function of (seed, graph) — resume on ANY mesh
    # size must be bit-exact
    g = erdos_renyi(64, 5.0, seed=1)
    key = jax.random.PRNGKey(2)
    ref = distributed_pagerank_counts(g, 0.25, 40, key)
    d = tempfile.mkdtemp(prefix="elastic_counts_")
    died = kill(distributed_pagerank_counts, g, 40, key, d, [3])
    res = dict(died=died, targets={})
    for p in (1, 2, 4):
        dp = d + f"_p{p}"
        shutil.copytree(d, dp)          # pristine kill dir per target
        r = distributed_pagerank_counts(g, 0.25, 40, key, mesh=submesh(p),
                                        checkpoint_dir=dp, resume=True,
                                        checkpoint_every=2)
        res["targets"][str(p)] = dict(
            shards=r.shards, restarts=r.restarts,
            rounds_equal=r.rounds == ref.rounds,
            zeta_equal=bool(np.array_equal(flat_zeta(ref, g.n),
                                           flat_zeta(r, g.n))),
            pi_equal=bool(np.array_equal(np.asarray(ref.pi),
                                         np.asarray(r.pi))))
    out["counts"] = res

    # improved: the default pools cover the walks, so tail_walks is 0 and
    # the run past Phase 1 is RNG-free — a mid-Phase-2 kill resumed on a
    # shrunk mesh must reproduce the unfailed run bit-exactly
    g2 = erdos_renyi(96, 5.0, seed=1)
    ref2 = distributed_improved_pagerank(g2, 0.25, 40, jax.random.PRNGKey(0))
    mid_p2 = (ref2.phase1_rounds + ref2.report_rounds
              + max(ref2.phase2_rounds // 2, 1))
    d2 = tempfile.mkdtemp(prefix="elastic_improved_")
    died2 = kill(distributed_improved_pagerank, g2, 40, jax.random.PRNGKey(0),
                 d2, [mid_p2])
    r2 = distributed_improved_pagerank(g2, 0.25, 40, jax.random.PRNGKey(0),
                                       mesh=submesh(4), checkpoint_dir=d2,
                                       resume=True, checkpoint_every=2)
    out["improved"] = dict(
        died=died2, fail_at=mid_p2, tail_walks=ref2.tail_walks,
        shards=r2.shards, restarts=r2.restarts, dropped=r2.dropped,
        zeta_equal=bool(np.array_equal(flat_zeta(ref2, g2.n),
                                       flat_zeta(r2, g2.n))),
        pi_equal=bool(np.array_equal(np.asarray(ref2.pi),
                                     np.asarray(r2.pi))))

    # directed: kill inside keyed Phase 1 — the resume re-derives fresh
    # per-shard key streams, so exactness is statistical: gate on the same
    # L1/top-10 conformance thresholds the launch --check uses
    g3 = directed_web(64, 5.0, seed=3)
    d3 = tempfile.mkdtemp(prefix="elastic_directed_")
    died3 = kill(distributed_directed_pagerank, g3, 20, jax.random.PRNGKey(3),
                 d3, [1])
    r3 = distributed_directed_pagerank(g3, 0.25, 20, jax.random.PRNGKey(3),
                                       mesh=submesh(4), checkpoint_dir=d3,
                                       resume=True, checkpoint_every=2)
    pi_ref, _, _ = power_iteration(g3, 0.25)
    pi3 = np.asarray(r3.pi, dtype=np.float64)
    out["directed"] = dict(
        died=died3, shards=r3.shards, restarts=r3.restarts,
        dropped=r3.dropped,
        l1=float(l1_error(pi3 / pi3.sum(), pi_ref)),
        topk=float(topk_overlap(pi3, np.asarray(pi_ref))))

    print(json.dumps(out))
""")


SERVE_CODE = textwrap.dedent("""
    import json
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core import l1_error, normalized, topk_overlap
    from repro.core.distributed import AXIS
    from repro.core.personalized import exact_ppr
    from repro.graphs import erdos_renyi
    from repro.serve.ppr_service import PPRService

    devs = jax.devices()
    def submesh(p):
        return Mesh(np.array(devs[:p]), (AXIS,))

    g = erdos_renyi(96, 5.0, seed=1)
    svc = PPRService(g, 0.25, slots=2, walks_per_query=4096,
                     mesh=submesh(4))
    r1 = svc.submit([3], now=0.0)
    r2 = svc.submit([10, 17], now=0.0)
    for _ in range(2):                  # both queries mid-flight
        svc.step(now=0.0)
    svc.resize(mesh=submesh(2))         # shrink the mesh under them
    r3 = svc.submit([5], now=0.0)       # admitted post-resize
    svc.drain(now=0.0)

    qs = dict(q1=(r1, [3]), q2=(r2, [10, 17]), q3=(r3, [5]))
    acc = {}
    for name, (req, sources) in qs.items():
        ref = exact_ppr(g, 0.25, sources)
        acc[name] = dict(
            done=req.done,
            l1=float(l1_error(normalized(req.result), normalized(ref))),
            topk=float(topk_overlap(req.result, ref)))
    # a post-resize cache hit serves the STORED pre-resize vector
    hit = svc.submit([3], now=0.0)
    out = dict(
        acc=acc, dropped=svc.stats.dropped_walks,
        admit_dropped=svc.stats.admit_dropped,
        completed=svc.stats.completed,
        cache_hit=bool(hit.cached),
        cache_bitexact=bool(np.array_equal(hit.result, r1.result)))
    print(json.dumps(out))
""")


GROW_CODE = textwrap.dedent("""
    import json, tempfile
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core.distributed import AXIS
    from repro.core.distributed_counts import distributed_pagerank_counts
    from repro.graphs import erdos_renyi
    from repro.runtime import SimulatedFailure

    devs = jax.devices()
    g = erdos_renyi(64, 5.0, seed=1)
    key = jax.random.PRNGKey(2)
    mesh8 = Mesh(np.array(devs[:8]), (AXIS,))
    ref = distributed_pagerank_counts(g, 0.25, 40, key, mesh=mesh8)
    d = tempfile.mkdtemp(prefix="elastic_grow_")
    died = False
    try:
        distributed_pagerank_counts(g, 0.25, 40, key, mesh=mesh8,
                                    checkpoint_dir=d, fail_at=[3],
                                    checkpoint_every=2, max_restarts=0)
    except SimulatedFailure:
        died = True
    mesh16 = Mesh(np.array(devs), (AXIS,))
    r = distributed_pagerank_counts(g, 0.25, 40, key, mesh=mesh16,
                                    checkpoint_dir=d, resume=True,
                                    checkpoint_every=2)
    fz = lambda x: np.asarray(x.zeta).reshape(-1)[:g.n]
    print(json.dumps(dict(
        died=died, shards=r.shards, restarts=r.restarts,
        rounds_equal=r.rounds == ref.rounds,
        zeta_equal=bool(np.array_equal(fz(ref), fz(r))))))
""")


@pytest.fixture(scope="module")
def payload():
    # hard-requires an 8-device mesh (shrink targets 1/2/4), so the count
    # is forced rather than REPRO_TEST_DEVICES-steered
    return run_forced_devices(ELASTIC_CODE, devices=8)


@pytest.fixture(scope="module")
def serve_payload():
    return run_forced_devices(SERVE_CODE, devices=8)


@pytest.mark.parametrize("target", ["1", "2", "4"])
def test_counts_elastic_resume_bit_exact(target, payload):
    """Kill at 8 shards, resume at P' — zeta/pi bit-identical to the
    unfailed 8-shard run, with no in-process restarts."""
    r = payload["counts"]
    assert r["died"], r
    t = r["targets"][target]
    assert t["shards"] == int(target), t
    assert t["restarts"] == 0, t
    assert t["zeta_equal"] and t["pi_equal"] and t["rounds_equal"], t


def test_improved_midphase2_elastic_resume_bit_exact(payload):
    """Phase 2 is RNG-free (and the tail empty under pools that cover the
    walks): a mid-Phase-2 kill resumed on 4 shards reproduces the 8-shard
    run bit for bit."""
    r = payload["improved"]
    assert r["died"], r
    assert r["tail_walks"] == 0, r       # precondition for exactness
    assert r["shards"] == 4 and r["restarts"] == 0, r
    assert r["zeta_equal"] and r["pi_equal"], r
    assert r["dropped"] == 0, r


def test_directed_keyed_elastic_resume_conformance(payload):
    """A kill in keyed Phase 1 forces key re-derivation: the resumed run is
    a fresh trajectory, gated by the launch --check tolerances."""
    r = payload["directed"]
    assert r["died"], r
    assert r["shards"] == 4 and r["restarts"] == 0, r
    assert r["dropped"] == 0, r
    assert r["l1"] < 0.15 and r["topk"] >= 0.6, r


def test_counts_elastic_resume_grows_mesh():
    """8 -> 16 shards (growing needs its own forced-16 process)."""
    r = run_forced_devices(GROW_CODE, devices=16)
    assert r["died"], r
    assert r["shards"] == 16 and r["restarts"] == 0, r
    assert r["zeta_equal"] and r["rounds_equal"], r


def test_ppr_service_resize_mid_traffic(serve_payload):
    """Shrinking the resident engine's mesh mid-flight drops nothing: in-
    flight queries finish on the new mesh within tolerance, and the cache
    keeps serving pre-resize vectors bit-identically."""
    r = serve_payload
    assert r["dropped"] == 0 and r["admit_dropped"] == 0, r
    assert r["completed"] == 3, r
    for name, a in r["acc"].items():
        assert a["done"], (name, a)
        assert a["l1"] < 0.15 and a["topk"] >= 0.6, (name, a)
    assert r["cache_hit"] and r["cache_bitexact"], r
