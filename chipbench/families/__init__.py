"""A model family as data: one module a ``model_type``, holding the scope
names its programs carry, the named groups of them the per-layer readers ask
for, and its counting functions under one set of names
(``chipbench/scopes.py`` says which). A configuration's family is the module
named after the ``model_type`` its file publishes — the key its plain
reference in ``chipbench/reference/`` is named after too — so a later
configuration of a new family brings ``families/<model_type>.py`` and joins
the readings by their ``workloads`` lists, with no reader and no harness
file edited."""

import importlib
from typing import Optional


def named(model_type: Optional[str]):
    """The family module ``families/<model_type>.py``, or None where there is
    none (or no name)."""
    if not model_type:
        return None
    try:
        return importlib.import_module(f"{__name__}.{model_type}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{model_type}":
            raise
        return None


def of(cfg: dict):
    """The family module of a configuration: the one named after the
    ``model_type`` its file publishes."""
    return named(cfg.get("model_type"))
