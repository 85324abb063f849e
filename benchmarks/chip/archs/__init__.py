"""What the benchmark knows of one architecture, one module each.

``archs/<arch>.py`` is found by a configuration's ``"arch"``, as
``metrics/<name>.py`` is by a metric's name, and gives:

* ``model_config(cfg)`` — the program's model config, from the JSON;
* ``init_params(key, cfg)`` — the weights, drawn inside the world's one
  jitted call (``worldgen``);
* ``tree_forward(params, x_levels, valid, fanouts, compute)`` — the plain
  reference over sampled trees, in ``reference.policy(compute)``;
* ``flops_per_seed(cfg)`` and ``aggregation_work(cfg)`` — the work the
  algorithm needs per seed, over ``workcount.layer_shapes``;
* ``kernel_calls_per_layer(cfg)`` — aggregation kernel calls per layer of
  one batch.
"""
from __future__ import annotations

import importlib.util
import os

DIR = os.path.dirname(os.path.abspath(__file__))

_loaded: dict = {}


def path(arch: str) -> str:
    return os.path.join(DIR, arch + ".py")


def load(arch: str):
    """The module of ``arch``, loaded once per file."""
    p = path(arch)
    if p not in _loaded:
        spec = importlib.util.spec_from_file_location(
            f"chipbench_arch_{arch.replace('.', '_').replace('-', '_')}", p)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[p] = mod
    return _loaded[p]


def of(cfg: dict):
    """The module of the configuration's architecture."""
    return load(cfg["arch"])
