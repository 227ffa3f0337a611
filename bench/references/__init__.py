"""Plain references, one module per model family, found by the ``family``
key of a configuration file: ``bench/references/<family>.py`` defines
``Reference(config, traffic, frozen_rows, precision)``."""
from __future__ import annotations

import importlib


def load(cell, precision: str = "float32"):
    mod = importlib.import_module(f"references.{cell.config['family']}")
    return mod.Reference(cell.config, cell.traffic, cell.frozen_rows(),
                         precision)
