"""The package graph of ``src/repro`` has one direction.

Each module's imports — function-level ones included — are read with
``ast`` and held to these layers:

* **leaves** — ``kernels``, ``obs``, ``resilience``, ``data`` and
  ``analyze.invariants`` (with the ``repro.analyze`` package, which only
  re-exports it) import no other ``repro`` package, except that
  ``analyze.invariants`` may import ``kernels``;
* **engine** — ``core`` and ``scale`` import only each other and the
  leaves.  The ``core`` <-> ``scale`` cycle is a debt: its edges are
  pinned below and may shrink but not grow;
* **service** — ``serve`` imports only the engine and the leaves;
* **top** — ``launch`` may import anything but the tools;
* **tools** — ``analyze.{lint,collectives,hlo,__main__}`` may import the
  engine and the leaves; no module outside them imports a tool.

No module changes ``jax.config`` at import: the process's settings belong
to its entry point (x64, say, makes Mosaic refuse every kernel).
"""
import ast
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parent.parent / "src"

LEAVES = ("kernels", "obs", "resilience", "data")
TOOLS = ("repro.analyze.lint", "repro.analyze.collectives",
         "repro.analyze.hlo", "repro.analyze.__main__")

# The core <-> scale cycle, edge by edge (importer -> imported).
CYCLE = {
    ("repro.core.homology", "repro.scale"),
    ("repro.core.homology", "repro.scale.budget"),
    ("repro.core.packed_reduce", "repro.scale.shard"),
    ("repro.scale.budget", "repro.core.filtration"),
    ("repro.scale.shard", "repro.core.filtration"),
    ("repro.scale.sparse_input", "repro.core.filtration"),
    ("repro.scale.tiles", "repro.core.filtration"),
}


def _module_name(path: pathlib.Path) -> str:
    parts = path.relative_to(SRC).with_suffix("").parts
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


MODULES = sorted(_module_name(p) for p in (SRC / "repro").rglob("*.py"))


def _is_module(name: str) -> bool:
    base = SRC.joinpath(*name.split("."))
    return base.with_suffix(".py").is_file() or (base / "__init__.py").is_file()


def _imports(module: str):
    """(line, imported module) for every ``repro`` import in ``module``."""
    path = SRC.joinpath(*module.split("."))
    is_pkg = (path / "__init__.py").is_file()
    path = path / "__init__.py" if is_pkg else path.with_suffix(".py")
    package = module if is_pkg else module.rpartition(".")[0]
    found = []
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            found += [(node.lineno, a.name) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package.split(".")[:len(package.split(".")) -
                                           (node.level - 1)]
                target = ".".join(base + ([node.module] if node.module
                                          else []))
            else:
                target = node.module or ""
            # ``from pkg import sub`` imports the submodule ``pkg.sub``
            subs = [f"{target}.{a.name}" for a in node.names
                    if _is_module(f"{target}.{a.name}")]
            found += [(node.lineno, t) for t in subs or [target]]
    return [(line, t) for line, t in found
            if t == "repro" or t.startswith("repro.")]


def _layer(module: str) -> str:
    if module in TOOLS:
        return "tools"
    if module in ("repro.analyze", "repro.analyze.invariants"):
        return "invariants"
    top = module.split(".")[1] if "." in module else ""
    if top in LEAVES:
        return top
    return {"core": "engine", "scale": "engine", "serve": "service",
            "launch": "top", "": "root"}[top]


def _allowed(module: str, target: str) -> bool:
    mine, theirs = _layer(module), _layer(target)
    if theirs == "tools":
        return mine == "tools"
    if mine == "top":
        return True
    if mine in LEAVES or mine == "root":
        return theirs == mine
    if mine == "invariants":
        return theirs in ("invariants", "kernels")
    below = set(LEAVES) | {"invariants", "engine"}
    if mine == "engine":
        if theirs != "engine":
            return theirs in below
        if module.split(".")[1] != target.split(".")[1]:
            return (module, target) in CYCLE
        return True
    if mine == "service":
        return theirs in below | {"service"}
    assert mine == "tools", module
    return theirs in below


def _config_updates_at_import(module: str):
    """Lines of ``jax.config`` writes that run when ``module`` is imported
    (module and class bodies; function bodies run only when called)."""
    path = SRC.joinpath(*module.split("."))
    path = (path / "__init__.py" if (path / "__init__.py").is_file()
            else path.with_suffix(".py"))
    hits = []

    def visit(node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return
        if isinstance(node, ast.Call) and \
                ast.unparse(node.func).endswith("config.update"):
            hits.append(node.lineno)
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = getattr(node, "targets", None) or [node.target]
            if any(ast.unparse(t).startswith("jax.config.")
                   for t in targets):
                hits.append(node.lineno)
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(ast.parse(path.read_text(), str(path)))
    return hits


@pytest.mark.parametrize("module", MODULES)
def test_module_respects_layers(module):
    broken = [f"{module}:{line} imports {target} "
              f"({_layer(module)} -> {_layer(target)})"
              for line, target in _imports(module)
              if not _allowed(module, target)]
    assert not broken, "\n".join(broken)
    writes = _config_updates_at_import(module)
    assert not writes, f"{module} writes jax.config at import, line(s) {writes}"
