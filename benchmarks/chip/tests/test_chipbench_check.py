"""The check that decides ``correct``, driven through a whole run of a
tiny cell on the CPU (the look for a chip skipped): the served answers
agree with the plain reference, the controls and faults do not."""
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip import harness, sampling, worldgen  # noqa: E402


def tiny_cell() -> harness.Cell:
    with open(os.path.join(harness.HERE, "configs",
                           "sage-reddit.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny", n_nodes=300, n_edges=2400, d_in=24,
               d_hidden=16, n_classes=5, fanouts=[3, 2])
    traffic = {"kind": "closed", "clients": 4, "seeds_per_request": 16,
               "popularity": {"law": "zipf", "s": 0.99}}
    e2e = [{"name": "seeds_per_s", "unit": "seeds/s"}]
    return harness.Cell("tiny.closed", 1, cfg, traffic, e2e, [])


def run(seed=2 ** 31 + 3):
    return harness.run_cell(tiny_cell(), seed, 0.5, False, time.monotonic(),
                            require_tpu=False, compile_cache=False)


def test_sampling_copy_matches_the_programs_draws():
    from repro.sparse.sampler import sample_forest
    cfg = tiny_cell().config
    world = worldgen.make_world(cfg, 5)
    indptr, indices = world.host_csr()
    seeds = np.array([0, 7, 299, 42, 42])
    keys = sampling.tree_keys(1234, seeds.size)
    levels, valid = sampling.sample_trees(indptr, indices, seeds, keys,
                                          cfg["fanouts"], key=99)
    trees = sample_forest(indptr, indices, seeds, cfg["fanouts"], key=99,
                          tree_keys=keys)
    got = np.concatenate(levels, axis=1)
    want = np.stack([t.node_ids for t in trees])
    np.testing.assert_array_equal(got, want)
    for h in range(len(cfg["fanouts"])):
        np.testing.assert_array_equal(
            valid[h], np.stack([t.hop_valid[h] for t in trees]))


def test_world_is_made_from_the_seed():
    cfg = tiny_cell().config
    a, b = worldgen.make_world(cfg, 9), worldgen.make_world(cfg, 9)
    c = worldgen.make_world(cfg, 10)
    np.testing.assert_array_equal(np.asarray(a.indices),
                                  np.asarray(b.indices))
    assert not np.array_equal(np.asarray(a.x), np.asarray(c.x))
    indptr, indices = a.host_csr()
    assert indptr[-1] == cfg["n_edges"] and indices.size == cfg["n_edges"]
    rows = np.repeat(np.arange(cfg["n_nodes"]), np.diff(indptr))
    assert not (rows == indices).any()                  # no self loops
    assert not np.asarray(a.x)[-1].any()                # ghost row


def test_served_answers_agree_with_the_reference():
    out = run()
    assert out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap_over_rms"]["value"] < 1e-4
    assert out["checks"]["bf16_exact_logit_share"]["value"] < \
        harness.BF16_SHARE_LIMIT
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("control", ["bf16"])
def test_control_fails_the_limit(control):
    cfg = tiny_cell().config
    world = worldgen.make_world(cfg, 4)
    csr = world.host_csr()

    class Handle:
        def __init__(self, rid):
            self.rid, self.seeds = rid, np.arange(16) * 7 + rid
            self.done, self.error = True, None
            self.result = np.zeros((16, cfg["n_classes"]))

    sample = [Handle(rid) for rid in range(16)]
    got = harness.compare(cfg, world, csr, 4, sample, control)
    assert got["bf16_share"] > harness.BF16_SHARE_LIMIT


def _alter_answers(monkeypatch):
    from repro.serve.batcher import ServeRequest
    finish = ServeRequest.finish

    def altered(self, result, t_done):
        result = result.copy()
        result[0, 0] += 0.5
        return finish(self, result, t_done)
    monkeypatch.setattr(ServeRequest, "finish", altered)


def _draws_off(monkeypatch):
    build = harness.build_server

    def other_key(cfg, world, csr, seed, **kw):
        return build(cfg, world, csr, seed + 1, **kw)
    monkeypatch.setattr(harness, "build_server", other_key)


def _aggregation_halved(monkeypatch):
    from repro.sparse import backend as sb
    aggregate = sb.aggregate

    def halved(*a, **kw):
        return 0.5 * aggregate(*a, **kw)
    monkeypatch.setattr(sb, "aggregate", halved)


@pytest.mark.parametrize("fault", [_alter_answers, _draws_off,
                                   _aggregation_halved])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run()
    assert not out["correct"]
    assert out["checks"]["max_logit_gap_over_rms"]["value"] > \
        harness.REL_ERR_LIMIT
