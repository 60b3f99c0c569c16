"""Test/bench harness utilities (in-process server, fixtures)."""

import json
import os
from typing import Any, Dict, Optional

from client_tpu.testing.flake import (  # noqa: F401
    rerun_on_grpc_poller_breakdown,
    retry_grpc_poller_flake,
)
from client_tpu.testing.inprocess import InProcessServer  # noqa: F401


def hermetic_child_env(base=None, repo_path=None):
    """Environment for hermetic-tier child processes: JAX pinned to the
    host backend (``JAX_PLATFORMS=cpu``) and, with ``repo_path``, this
    checkout first on ``PYTHONPATH``. Device-tier runs must NOT use this.
    """
    env = dict(os.environ if base is None else base)
    if repo_path:
        env["PYTHONPATH"] = (
            repo_path + os.pathsep + env.get("PYTHONPATH", "")
        )
    env["JAX_PLATFORMS"] = "cpu"
    return env


def parse_server_started(line: str) -> Optional[Dict[str, Any]]:
    """The ``server_started`` lifecycle event, or None for any other line.

    ``python -m client_tpu.server`` logs the event (one JSON line from the
    structured logger, stderr by default) once both front-ends are bound;
    launchers that asked for port 0 read ``http_port``/``grpc_port`` from
    it instead of scraping prose.
    """
    if "server_started" not in line:
        return None
    try:
        event = json.loads(line)
        if event["event"] != "server_started":
            return None
        event["http_port"] = int(event["http_port"])
        event["grpc_port"] = int(event["grpc_port"])
    except (ValueError, KeyError, TypeError):
        return None
    return event
