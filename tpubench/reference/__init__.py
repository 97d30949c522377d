"""Plain references, one module per family, named by a configuration's
``reference`` key."""

from __future__ import annotations

import importlib
from types import ModuleType
from typing import Any, Dict


def load_reference(config: Dict[str, Any]) -> ModuleType:
    return importlib.import_module(f"reference.{config['reference']}")
