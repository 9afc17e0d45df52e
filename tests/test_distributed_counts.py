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
