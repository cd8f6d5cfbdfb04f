"""What belongs to one model family, found by the configuration's
``model_type``: ``bench/families/<model_type>.py`` maps the published
config.json to the program's ArchConfig, gives the weight tree's leaf
shapes and counts the matmul weights; ``bench/reference/<model_type>.py``
is the family's plain reference. A new family adds those two files."""
from __future__ import annotations

import importlib


def _module(package: str, conf: dict):
    name = conf["model_type"]
    try:
        return importlib.import_module(f"bench.{package}.{name}")
    except ModuleNotFoundError as e:
        raise SystemExit(f"bench: no {package} module for model_type "
                         f"{name!r}") from e


def get(conf: dict):
    """The family module of a configuration."""
    return _module("families", conf)


def reference(conf: dict):
    """The plain reference module of a configuration's family."""
    return _module("reference", conf)
