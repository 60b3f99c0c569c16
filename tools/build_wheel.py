#!/usr/bin/env python3
"""Build the client-tpu wheel, bundling the native artifacts.

Role parity with the reference's wheel assembly
(reference src/python/library/build_wheel.py:107-180 + setup.py:46-76): the
wheel carries the pure-Python client, the generated protobuf modules, and —
when the native tree is built — libcshm_tpu.so plus the perf_analyzer
binary under client_tpu/_native/, with a platform-specific wheel tag.
No sed-patching of generated code is needed (protos are staged package-
correct at generation time, see tools/gen_protos.sh).

Usage: python tools/build_wheel.py [--skip-native] [--dist-dir dist]
"""

import argparse
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cache_is_foreign(build_dir: str, source_dir: str) -> bool:
    """True when ``build_dir``'s CMakeCache.txt was written for another
    source or build path — what a copied checkout carries."""
    recorded = {}
    try:
        with open(os.path.join(build_dir, "CMakeCache.txt")) as f:
            for line in f:
                key, sep, value = line.strip().partition(":INTERNAL=")
                if sep:
                    recorded[key] = value
    except FileNotFoundError:
        return False
    expected = {
        "CMAKE_HOME_DIRECTORY": source_dir,
        "CMAKE_CACHEFILE_DIR": build_dir,
    }
    return any(
        os.path.realpath(recorded.get(key, "")) != os.path.realpath(path)
        for key, path in expected.items()
    )


def build_native(build_dir: str, targets=(), **run_kwargs) -> None:
    """Configure and build ``native/`` into ``build_dir`` (every target,
    or just ``targets``). A build directory whose cache names another
    path is reconfigured from scratch, not trusted: CMake refuses to
    reuse it, and its binaries bake in the other checkout's root.
    ``run_kwargs`` go to both ``subprocess.run`` calls."""
    source_dir = os.path.join(REPO, "native")
    configure = ["cmake", "-S", source_dir, "-B", build_dir, "-G", "Ninja"]
    if _cache_is_foreign(build_dir, source_dir):
        configure.insert(1, "--fresh")
    subprocess.run(configure, check=True, **run_kwargs)
    subprocess.run(
        ["ninja", "-C", build_dir, *targets], check=True, **run_kwargs
    )


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--skip-native", action="store_true",
                        help="pure-Python wheel (no .so / perf_analyzer)")
    parser.add_argument("--dist-dir", default=os.path.join(REPO, "dist"))
    args = parser.parse_args()

    native_dir = os.path.join(REPO, "client_tpu", "_native")
    # Clean any previous staging: a stale _native/ in the source tree or a
    # stale setuptools build/lib would silently leak platform binaries into
    # a py3-none-any wheel.
    shutil.rmtree(native_dir, ignore_errors=True)
    for stale in ("lib",) + tuple(
        d for d in (os.listdir(os.path.join(REPO, "build"))
                    if os.path.isdir(os.path.join(REPO, "build")) else [])
        if d.startswith("bdist.")
    ):
        shutil.rmtree(os.path.join(REPO, "build", stale), ignore_errors=True)

    platform_tag = None
    try:
        if not args.skip_native:
            build_dir = os.path.join(REPO, "build")
            build_native(build_dir)
            os.makedirs(native_dir, exist_ok=True)
            for artifact in ("libcshm_tpu.so", "perf_analyzer"):
                src = os.path.join(build_dir, artifact)
                if not os.path.exists(src):
                    print(f"error: missing native artifact {src}",
                          file=sys.stderr)
                    return 1
                shutil.copy2(src, os.path.join(native_dir, artifact))
            with open(os.path.join(native_dir, "__init__.py"), "w") as f:
                f.write(
                    '"""Bundled native artifacts '
                    '(see tools/build_wheel.py)."""\n'
                )
            import sysconfig

            platform_tag = sysconfig.get_platform().replace(
                "-", "_"
            ).replace(".", "_")

        cmd = [sys.executable, "-m", "build", "--wheel", "--no-isolation",
               "--outdir", args.dist_dir]
        if platform_tag:
            cmd += ["--config-setting=--build-option=--plat-name",
                    f"--config-setting=--build-option={platform_tag}"]
        subprocess.run(cmd, check=True, cwd=REPO)
    finally:
        shutil.rmtree(native_dir, ignore_errors=True)
        shutil.rmtree(os.path.join(REPO, "build", "lib"), ignore_errors=True)

    wheels = sorted(
        f for f in os.listdir(args.dist_dir) if f.endswith(".whl")
    )
    print("built:", ", ".join(wheels))
    return 0


if __name__ == "__main__":
    sys.exit(main())
