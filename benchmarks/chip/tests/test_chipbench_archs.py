"""The harness's architecture modules (``archs/``).

GraphSAGE's readings are bit for bit those recorded before its share of the
harness moved into ``archs/sage.py`` (``data/sage_parent_digest.json``: a
tiny world, its weights, the reference's outputs on fixed trees, and the
work counts of both configurations).  A second architecture name runs
through a whole run by adding its module alone, and a configuration that
names no module fails before any device data is made."""
import hashlib
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

from benchmarks.chip import (archs, harness, reference, sampling,  # noqa: E402
                             workcount, worldgen)

with open(os.path.join(HERE, "data", "sage_parent_digest.json")) as _f:
    PINNED = json.load(_f)


def digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return (f"{a.dtype}{list(a.shape)}:"
            + hashlib.sha256(a.tobytes()).hexdigest())


def config(name: str) -> dict:
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def tiny_config(name: str) -> dict:
    sizes = dict(PINNED["tiny"][name])
    cfg = config(sizes.pop("base"))
    cfg.update(name=name, **sizes)
    return cfg


@pytest.mark.parametrize("name", sorted(PINNED["worlds"]))
def test_tiny_world_and_reference_match_the_pinned_digest(name):
    import jax
    cfg, want = tiny_config(name), PINNED["worlds"][name]
    world = worldgen.make_world(cfg, PINNED["seed"])
    indptr, indices = world.host_csr()
    assert digest(indptr) == want["indptr"]
    assert digest(indices) == want["indices"]
    assert digest(world.x) == want["x"]
    assert {f"{k}/{n}": digest(v) for k, p in sorted(world.params.items())
            for n, v in sorted(p.items())} == want["params"]
    trees = PINNED["trees"]
    seeds = np.arange(20) * 13 % cfg["n_nodes"]
    keys = sampling.tree_keys(trees["tree_keys_rid"], seeds.size)
    levels, valid = sampling.sample_trees(indptr, indices, seeds, keys,
                                          cfg["fanouts"],
                                          trees["sampler_key"])
    params = jax.tree.map(np.asarray, world.params)
    got = {c: digest(reference.reference_outputs(
        cfg, world.x, params, levels, valid, c, block=trees["block"]))
        for c in reference.COMPUTES}
    assert got == want["reference"]


@pytest.mark.parametrize("name", sorted(PINNED["work"]))
def test_work_numbers_match_the_pinned_counts(name):
    cfg, want = config(name), PINNED["work"][name]
    with open(os.path.join(harness.HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    arch = archs.of(cfg)
    work = arch.aggregation_work(cfg)
    assert arch.flops_per_seed(cfg) == want["flops_per_seed"]
    assert work == want["aggregation_work"]
    assert workcount.aggregation_floor_s(
        work, peaks["bf16_flops"], peaks["hbm_bytes_per_s"]) == \
        want["aggregation_floor_s"]
    assert arch.kernel_calls_per_layer(cfg) == 1


def test_a_second_arch_runs_by_adding_its_module_alone(tmp_path,
                                                        monkeypatch):
    # ``sage-copy`` is served by the program as ``sage``; the benchmark
    # finds only the one file put in an otherwise empty ``archs``
    shutil.copy(archs.path("sage"), tmp_path / "sage-copy.py")
    monkeypatch.setattr(archs, "DIR", str(tmp_path))
    cfg = tiny_config("tiny")
    cfg["arch"] = "sage-copy"
    traffic = {"kind": "closed", "clients": 4, "seeds_per_request": 16,
               "popularity": {"law": "zipf", "s": 0.99}}
    cell = harness.Cell("tiny-copy.closed", 1, cfg, traffic,
                        [{"name": "seeds_per_s", "unit": "seeds/s"}], [])
    out = harness.run_cell(cell, 2 ** 31 + 5, 0.5, False, time.monotonic(),
                           require_tpu=False, compile_cache=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["metrics"]["seeds_per_s"]["value"]
    assert archs.of(cfg).__file__ == str(tmp_path / "sage-copy.py")


def test_a_config_naming_no_arch_module_fails_before_device_data(
        tmp_path, monkeypatch, capsys):
    from benchmarks.chip import run
    cfg = tiny_config("tiny")
    cfg["arch"] = "gat"
    (tmp_path / "tiny.json").write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny", "file": "tiny.json"}],
        "workloads": [{"name": "tiny.closed", "config": "tiny",
                       "traffic": "closed-8x16", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    monkeypatch.setattr(archs, "DIR", str(tmp_path / "archs"))
    load = harness.load_cell
    monkeypatch.setattr(harness, "load_cell",
                        lambda w: load(w, root=str(tmp_path)))

    def no_world(*a, **kw):
        raise AssertionError("device data made for a cell with no arch")
    monkeypatch.setattr(worldgen, "make_world", no_world)
    with pytest.raises(harness.HarnessError, match=r"archs/gat\.py"):
        harness.load_cell("tiny.closed")
    assert run.main(["--workload", "tiny.closed", "--seed", "1",
                     "--seconds", "1"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "archs/gat.py" in err
