"""The benchmark's own description of a configuration: sizes, the leaves
of each layer and how the seed makes them.

Read from ``bench/configs/<name>.json`` (the keys of the model's public
config), whose ``arch`` key names the module in ``bench/arch/`` that
describes it.  The reference, the weights and the FLOP counts use this
and nothing of the program; ``program.py`` alone maps it onto the
program.
"""
from __future__ import annotations

import importlib
import json
import math
import os
from types import ModuleType
from typing import Dict, List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))

# A leaf: (dotted path, shape, init, dtype).  init is ("normal", std) or
# ("ones",).
Leaf = Tuple[str, Tuple[int, ...], tuple, str]


def load_config(name: str) -> dict:
    path = os.path.join(HERE, "configs", f"{name}.json")
    with open(path) as f:
        c = json.load(f)
    _module(c, path)
    return c


def _module(c: dict, where: str) -> ModuleType:
    """The module ``bench/arch/<arch>.py`` that the configuration's
    ``arch`` key names; an error that names ``where`` if there is none."""
    arch = c.get("arch")
    if not isinstance(arch, str) or not arch.isidentifier():
        raise ValueError(f"{where}: 'arch' must name a module in bench/arch/"
                         f", not {arch!r}")
    try:
        return importlib.import_module(f"bench.arch.{arch}")
    except ModuleNotFoundError as e:
        if e.name != f"bench.arch.{arch}":
            raise
        raise ValueError(f"{where}: unknown arch {arch!r}: there is no "
                         f"bench/arch/{arch}.py") from e


def from_config(c: dict):
    """The model, as the configuration's architecture module describes
    it."""
    return _module(c, c.get("name", "the configuration")).describe(c)


def arch(m) -> ModuleType:
    """The architecture module that described ``m``."""
    return importlib.import_module(f"bench.arch.{m.arch}")


def layer_leaves(m, g: int) -> List[Leaf]:
    """The leaves of global layer ``g``, in a fixed order (the order keys
    the seed).  Names are those of the program's parameter tree."""
    return arch(m).layer_leaves(m, g)


def shared_leaves(m) -> List[Leaf]:
    return arch(m).shared_leaves(m)


def decays(m, path: str, shape) -> bool:
    """AdamW's decay rule of ``m``'s architecture."""
    return arch(m).decays(path, shape)


def matmul_params(m) -> Dict[str, int]:
    return arch(m).matmul_params(m)


def layer_params(m, g: int = 0) -> int:
    return sum(math.prod(s) for _, s, _, _ in layer_leaves(m, g))
