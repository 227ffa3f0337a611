"""Model families, one module each, found by the ``family`` key of a
configuration file: ``bench/families/<family>.py``, and its plain reference
``bench/references/<family>.py`` by the same name.  A family holds what the
harness knows of one architecture, and nothing outside it reads a width:

* ``model_config(cell)``: the program's ``ModelConfig``;
* ``shapes(config)``: each weight leaf's shape and fan-in axis, which
  ``bench/weights.py`` makes from the seed;
* the work one step requires, which ``bench/flops.py`` hands on:
  ``step_flops``, ``attention_flops_per_call``, ``flash_bytes_per_call``,
  ``grades_bytes_per_step`` and ``frozen_share``, each of a cell;
* ``frozen_masks(cell)``: the program's monitor group of each matrix type
  the traffic freezes, with its frozen flags at the group's own mask shape
  (``(L,)`` per layer, ``(L, E)`` per layer and expert).

A new family adds its module here, its reference under
``bench/references/`` and data files; no other file changes.
"""
from __future__ import annotations

import importlib


def load(config, where=None):
    """The family module that ``config`` names.  A missing or unknown family
    is refused, naming ``where`` (the configuration file)."""
    where = where or f"configuration {config.get('name')!r}"
    name = config.get("family")
    if not isinstance(name, str) or not name.isidentifier():
        raise SystemExit(f"{where}: no model family (the 'family' key reads "
                         f"{name!r}); it names bench/families/<family>.py")
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise SystemExit(f"{where}: unknown model family {name!r}: there is "
                         f"no bench/families/{name}.py") from None
