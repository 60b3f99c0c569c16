"""Reading a configuration's `config.json` (JAX-free: the parent uses it)."""

import json
import os


def load_config(config_dir: str, toy: bool = False) -> dict:
    """`config.json` as run: in a CPU rehearsal the ``toy`` group's keys
    replace the same keys of ``model`` and ``engine``."""
    with open(os.path.join(config_dir, "config.json")) as f:
        config = json.load(f)
    if toy:
        for group, keys in config.get("toy", {}).items():
            config[group] = {**config.get(group, {}), **keys}
    config.pop("toy", None)
    return config
