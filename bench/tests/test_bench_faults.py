"""`correct` comes out false for the control and for every fault a cell can
have, with the timed path broken underneath a whole run (device check
skipped, tiny sizes on the CPU).

The control breaks the guarantee that every walk runs until it ends
(bench/control.py): batch jobs through the engine's own round cap. The
faults: a step that returns its state unchanged; half of the batch left
out, the rest scaled to stand for it; an answer altered where it is
produced. The cell runs on one chip, so there is no exchange between
chips to leave out.
"""
import numpy as np
import pytest

import repro.core.distributed_counts as dc
from bench import control, harness
from bench.tests.tiny import cells, tiny_root

SEED = 2**33 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


def _correct(root, cell):
    out = harness.run(cell, SEED, 2.0, False, root=root, require_tpu=False)
    return out["correct"], out["checks"]


@pytest.mark.parametrize("cell", cells())
def test_sound_run_is_correct(root, cell):
    ok, checks = _correct(root, cell)
    assert ok, checks


def _cap_rounds(real, graph, eps, k, key, **kw):
    kw["max_rounds"] = min(kw["max_rounds"], control.control_steps(eps))
    return real(graph, eps, k, key, **kw)


def _half_left_out(real, graph, eps, k, key, **kw):
    r = real(graph, eps, k, key, **kw)
    pi = np.asarray(r.pi).copy()
    pi[1::2] = 0.0
    pi[0::2] *= 2.0
    r.pi = pi
    return r


def _answer_altered(real, graph, eps, k, key, **kw):
    r = real(graph, eps, k, key, **kw)
    r.pi = np.roll(np.asarray(r.pi), 1)
    return r


def _state_unchanged(real, graph, eps, k, key, **kw):
    make = dc.make_count_superstep

    def frozen(*a, **kw2):
        sample, exchange = make(*a, **kw2)

        def stuck(bnbr, flat_T, key2, state):
            new, active, entries, a2a, ovf = exchange(bnbr, flat_T, key2,
                                                      state)
            new.counts, new.zeta = state.counts, state.zeta
            return new, active + 1, entries, a2a, ovf
        return sample, stuck
    dc.make_count_superstep = frozen
    try:
        return real(graph, eps, k, key, **kw)
    finally:
        dc.make_count_superstep = make


@pytest.mark.parametrize("fault", [_cap_rounds, _half_left_out,
                                   _answer_altered, _state_unchanged])
def test_batch_control_and_faults_are_not_correct(root, monkeypatch, fault):
    real = dc.distributed_pagerank_counts
    monkeypatch.setattr(dc, "distributed_pagerank_counts",
                        lambda *a, **kw: fault(real, *a, **kw))
    ok, checks = _correct(root, "batch.g500")
    assert not ok, checks

