"""World generators: the deployments the cells run on, made from ``--seed``.

One generator per ``kind`` named in a traffic file's ``world`` group; a
later PR adds a kind as a new module here, found by name."""

from __future__ import annotations

import importlib


def build(config: dict, world: dict, seed: int):
    """The world a cell runs on: ``config`` is the configuration file,
    ``world`` the traffic file's ``world`` group (its ``kind`` names the
    module under ``benchmark/worlds/``)."""
    module = importlib.import_module(f"benchmark.worlds.{world['kind']}")
    return module.build(config, world, int(seed))
