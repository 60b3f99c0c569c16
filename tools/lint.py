"""Minimal dependency-free lint: syntax + unused-import scan.

The pre-commit/CI lint gate (role of the reference's flake8/isort hooks,
reference .pre-commit-config.yaml) for zero-egress environments where
external linters cannot be installed. Checks every tracked .py file for
(a) syntax errors and (b) imports never referenced in the module.
"""

import ast
import os

ROOTS = ["client_tpu", "tools", "tests", "examples/model_repository",
         "chip_smoke.py", "__graft_entry__.py"]
# Imports with side effects or re-export duties.
ALLOWED_UNUSED = {"client_tpu", "conftest"}


def iter_py_files():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for root in ROOTS:
        path = os.path.join(repo, root)
        if os.path.isfile(path):
            yield path
        else:
            for dirpath, _dirs, files in os.walk(path):
                if "_generated" in dirpath or "__pycache__" in dirpath:
                    continue
                for f in files:
                    if f.endswith(".py"):
                        yield os.path.join(dirpath, f)


def unused_imports(tree: ast.AST, source: str):
    imported = {}  # name -> (first, last) line of its import statement
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Import, ast.ImportFrom)):
            continue
        span = (node.lineno, node.end_lineno)
        for alias in node.names:
            if isinstance(node, ast.Import):
                imported[(alias.asname or alias.name).split(".")[0]] = span
            elif alias.name != "*":
                imported[alias.asname or alias.name] = span
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            pass  # attribute bases appear as Name nodes already
    # __all__ re-exports and noqa'd lines count as used.
    noqa_lines = {
        i + 1
        for i, line in enumerate(source.splitlines())
        if "noqa" in line
    }
    for name, (lineno, last) in sorted(imported.items()):
        if name in used or name in ALLOWED_UNUSED:
            continue
        # a parenthesised import may carry its noqa on any of its lines
        if noqa_lines.intersection(range(lineno, last + 1)):
            continue
        if f'"{name}"' in source or f"'{name}'" in source:
            continue  # appears in __all__ or string registry
        yield name, lineno


def main() -> int:
    failures = 0
    for path in iter_py_files():
        with open(path, encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            print(f"{path}:{e.lineno}: syntax error: {e.msg}")
            failures += 1
            continue
        for name, lineno in unused_imports(tree, source):
            print(f"{path}:{lineno}: unused import '{name}'")
            failures += 1
    if failures:
        print(f"lint: {failures} finding(s)")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
