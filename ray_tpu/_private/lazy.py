"""Package exports that resolve on first use (PEP 562).

A driver process must be able to import the orchestration half of a
package (trainers, configs) without importing the half that needs JAX:
the chip belongs to the workers.
"""

from __future__ import annotations

import importlib
from typing import Dict, Sequence


def lazy_exports(package: str, exports: Dict[str, Sequence[str]]):
    """``exports`` maps a module to the names it provides.  Returns
    ``(__all__, __getattr__)`` for ``package``'s ``__init__``."""
    where = {name: mod for mod, names in exports.items() for name in names}

    def __getattr__(name):
        if name not in where:
            raise AttributeError(
                f"module {package!r} has no attribute {name!r}")
        return getattr(importlib.import_module(where[name]), name)

    return list(where), __getattr__
