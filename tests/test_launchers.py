"""Launcher plumbing every entry point shares: where the compile cache
lives, how a launcher learns a child server's ports, and when a native
build directory can be trusted."""

import json
import os
import subprocess
import sys

from client_tpu.compile_cache import ENV_CACHE_DIR
from client_tpu.testing import hermetic_child_env, parse_server_started

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_REPORT_CACHE = (
    "import json, jax\n"
    "from client_tpu.compile_cache import enable_compile_cache\n"
    "returned = enable_compile_cache()\n"
    "print(json.dumps({'returned': returned,\n"
    "    'config': jax.config.jax_compilation_cache_dir}))\n"
)


def _cache_report(cache_env):
    env = hermetic_child_env(repo_path=REPO)
    env.pop(ENV_CACHE_DIR, None)
    if cache_env is not None:
        env[ENV_CACHE_DIR] = cache_env
    out = subprocess.run(
        [sys.executable, "-c", _REPORT_CACHE],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    )
    return json.loads(out.stdout.splitlines()[-1])


def test_compile_cache_placed_from_outside_is_untouched(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the helper sets nothing: JAX's
    own reading of the variable is what the process uses."""
    outside = str(tmp_path / "cache")
    report = _cache_report(outside)
    assert report == {"returned": outside, "config": outside}
    assert not os.path.exists(outside)  # nothing compiled, nothing made


def test_compile_cache_default_is_one_fixed_path():
    """Unset, two processes agree on <checkout>/.jax_cache — the path is
    part of the cache key, so a tmp name, pid or time would never hit."""
    first, second = _cache_report(None), _cache_report(None)
    expected = os.path.join(REPO, ".jax_cache")
    assert first == second == {"returned": expected, "config": expected}


def test_parse_server_started_reads_ports_from_the_event():
    line = json.dumps({
        "ts": 1790413134.19, "severity": "INFO", "event": "server_started",
        "logger": "server", "host": "127.0.0.1", "http_port": 44493,
        "grpc_port": "38457", "grpc_frontend": "native",
    })
    event = parse_server_started(line + "\n")
    assert (event["http_port"], event["grpc_port"]) == (44493, 38457)
    assert event["grpc_frontend"] == "native"


def test_parse_server_started_ignores_everything_else():
    for line in (
        "",
        "listening http=127.0.0.1:8000 grpc=127.0.0.1:8001",
        json.dumps({"event": "drain_started", "note": "server_started"}),
        "WARNING: server_started but this is not json",
        json.dumps({"event": "server_started", "http_port": 1}),
        json.dumps({"event": "server_started", "http_port": "x",
                    "grpc_port": 2}),
        json.dumps(["server_started"]),
    ):
        assert parse_server_started(line) is None, line


def test_native_build_dir_from_another_checkout_is_not_trusted(tmp_path):
    from tools.build_wheel import _cache_is_foreign

    source, build = str(tmp_path / "native"), str(tmp_path / "build")
    os.makedirs(build)
    assert not _cache_is_foreign(build, source)  # no cache yet

    def write_cache(home, cachefile_dir):
        with open(os.path.join(build, "CMakeCache.txt"), "w") as f:
            f.write("# comment\nCMAKE_BUILD_TYPE:STRING=Release\n")
            f.write(f"CMAKE_CACHEFILE_DIR:INTERNAL={cachefile_dir}\n")
            f.write(f"CMAKE_HOME_DIRECTORY:INTERNAL={home}\n")

    write_cache(source, build)
    assert not _cache_is_foreign(build, source)
    write_cache("/root/repo/native", "/root/repo/build")
    assert _cache_is_foreign(build, source)
    write_cache(source, "/somewhere/else/build")
    assert _cache_is_foreign(build, source)
