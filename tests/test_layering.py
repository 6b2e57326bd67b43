"""One-way imports. The SVM serving path loads no module of the
language-model sidecar (``repro.{models,configs,train,sharding,launch}``,
``repro.serve.decode_step``, ``repro.data.loader``), and the kernel
layer imports nothing from the layers above it."""

import ast
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")

SVM_PACKAGES = (
    "repro.serve",
    "repro.serve.runtime",
    "repro.serve.server",
    "repro.core",
    "repro.core.families",
    "repro.kernels.common",
    "repro.data",
    "repro.svm",
)
SIDECAR = (
    "repro.models",
    "repro.configs",
    "repro.train",
    "repro.sharding",
    "repro.launch",
    "repro.serve.decode_step",
    "repro.data.loader",
)
ABOVE_KERNELS = ("repro.core", "repro.serve", "repro.launch")


def _within(name, packages):
    return any(name == p or name.startswith(p + ".") for p in packages)


def test_the_svm_packages_load_no_sidecar_module():
    # a fresh interpreter: this process has already imported whatever
    # other test files needed
    code = (
        "import importlib, json, sys\n"
        f"for name in {list(SVM_PACKAGES)!r}:\n"
        "    importlib.import_module(name)\n"
        "print(json.dumps(sorted(m for m in sys.modules if m.startswith('repro'))))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
    run = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert run.returncode == 0, run.stderr
    loaded = json.loads(run.stdout.splitlines()[-1])
    assert "repro.serve.svm_engine" in loaded  # the imports really ran
    assert [m for m in loaded if _within(m, SIDECAR)] == []


def _imported_names(path):
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            yield node.module
            # ``from repro import launch`` names the package in the alias
            yield from (f"{node.module}.{alias.name}" for alias in node.names)


def test_the_kernel_layer_imports_nothing_above_it():
    found = []
    for root, _, files in os.walk(os.path.join(SRC, "repro", "kernels")):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            found += [
                (os.path.relpath(path, SRC), imported)
                for imported in _imported_names(path)
                if _within(imported, ABOVE_KERNELS)
            ]
    assert found == []
