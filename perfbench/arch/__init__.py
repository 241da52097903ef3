"""Trunk architectures, one module each, found by name.

A configuration file names its trunk's architecture under the optional
top-level key ``"arch"`` (default ``dense_gqa``), and ``resolve`` loads
``arch/<arch>.py``. A new architecture is a new file here. Each module
holds, for its architecture:

* ``READS``: the trunk keys it reads, each one required;
* ``FIXED``: the keys it accepts only at one value, the one the program
  and the reference compute, each one optional;
* ``INFO``: keys that describe the published checkpoint and change
  nothing computed;
* ``llm_config(cfg)``: the trunk block as the program's ``ModelConfig``;
* ``trunk(params, cfg, ctx, query, tokens, mode="ref")``: the float32
  reference at ``highest`` precision, ``mode="fp8"`` its control;
* ``token_flops(pcfg, ctx)`` and ``paged_attention_cost(pcfg,
  ctx_lens)``: operations and bytes from shapes (``flops.py``'s
  convention).

A trunk key that none of the three lists names, a fixed key at another
value, a read key left out and an unknown architecture each fail here,
before anything is built: nothing a configuration states is dropped.
"""
from __future__ import annotations

import importlib
import os
from types import ModuleType
from typing import Any, Dict, List

DEFAULT = "dense_gqa"


def names() -> List[str]:
    """The architectures that have a module in this package."""
    return sorted({f[:-3] for d in __path__ for f in os.listdir(d)
                   if f.endswith(".py") and f != "__init__.py"})


def _check_keys(name: str, mod: ModuleType, trunk: Dict[str, Any]) -> None:
    known = set(mod.READS) | set(mod.FIXED) | set(mod.INFO)
    for key in trunk:
        if key not in known:
            raise ValueError(
                f"trunk key {key!r} is not read by architecture {name!r} "
                f"(it reads {sorted(known)})")
    for key in mod.READS:
        if key not in trunk:
            raise ValueError(f"trunk key {key!r}, which architecture "
                             f"{name!r} reads, is missing")
    for key, value in mod.FIXED.items():
        if key in trunk and trunk[key] != value:
            raise ValueError(
                f"trunk key {key!r} is {trunk[key]!r}; architecture "
                f"{name!r} serves it only at {value!r}")


def resolve(cfg: Dict[str, Any]) -> ModuleType:
    """The architecture module of a configuration, its trunk keys
    checked against what the module declares."""
    name = cfg.get("arch", DEFAULT)
    if not (isinstance(name, str) and name.isidentifier()
            and name in names()):
        raise ValueError(f"no trunk architecture {name!r}; perfbench/arch "
                         f"holds {names()}")
    mod = importlib.import_module(f"{__name__}.{name}")
    _check_keys(name, mod, cfg["trunk"])
    return mod
