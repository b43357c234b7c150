"""The benchmark's way into the program: its model configuration and its
parameter tree for a configuration file, through the file's model family
(``bench/models/<model_type>.py``), and the check that the benchmark's
weights have the program's own layout.

The program is imported from ``src/`` of the checkout.
"""
from __future__ import annotations

import os
import sys

from bench.common import ROOT, family

sys.path.insert(0, os.path.join(ROOT, "src"))


def model_config(cfg):
    """The program's ``ModelConfig`` for a configuration file."""
    return family(cfg).program_config(cfg)


def to_program(cfg, p):
    """Benchmark layout -> the program's parameter tree (no copies)."""
    return family(cfg).to_program(p)


def from_program(cfg, tree):
    """The program's parameter tree -> benchmark layout."""
    return family(cfg).from_program(tree)


def check_layout(jax, cfg, model, params):
    """Raise unless ``to_program(cfg, params)`` has the tree structure and
    shapes of the program's own initialisation."""
    want = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: to_program(cfg, params))
    ws = jax.tree.structure(want)
    gs = jax.tree.structure(got)
    if ws != gs:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{gs}\n{ws}")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a} against the program's {b}")
