"""The package's imports point one way.

An AST walk of `ddp_practice_tpu/` (imports inside functions count):

    cli, generate -> train, serve -> models -> ops, parallel -> utils

- no module of the package imports `tools`, `perf`, `tests`,
  `experiments` or a root script: those read the package, never the
  other way round;
- `utils`, `ops`, `parallel`, `models`, `data`, `checkpoint` import
  nothing from `serve`, `train`, `cli`, `generate`;
- `serve` and `train` do not import each other, and `train` imports
  neither entry point;
- `ops` imports nothing from `parallel` (the other way is allowed).

The upward edges that exist are listed in KNOWN_DEBTS, each with the
ROADMAP item that retires it. A new one fails its layer's case; so does
a listed one that has gone (take it out of the table).
"""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "ddp_practice_tpu"

OUTSIDE = {"tools", "perf", "tests", "experiments", "chip_smoke",
           "__graft_entry__", "bench"}
ABOVE = {"serve", "train", "cli", "generate"}
# layer -> the package layers it must not import
FORBIDDEN = {
    "utils": ABOVE,
    "ops": ABOVE | {"parallel"},
    "parallel": ABOVE,
    "models": ABOVE,
    "data": ABOVE,
    "checkpoint": ABOVE,
    "serve": {"train"},
    "train": {"serve", "cli", "generate"},
}
# (importing file, imported module) -> the ROADMAP item that retires it
KNOWN_DEBTS = {
    ("utils/telemetry.py", "serve.fairshare"): "D12",
    ("train/loop.py", "serve.slo"): "D12",
    ("ops/attention.py", "parallel.ring"): "D12",
    ("ops/attention.py", "parallel.ulysses"): "D12",
    ("ops/moe.py", "parallel.ring"): "D12",
}


def imported_modules(source: str, package: list) -> set:
    """Dotted names a module's source imports, relative ones resolved
    against `package` (the dotted path of the directory it sits in)."""
    found = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1]
            stem = ".".join((base if node.level else [])
                            + ([node.module] if node.module else []))
            found.add(stem)
            # `from pkg import sub` names a module as well
            found.update(f"{stem}.{a.name}" for a in node.names)
    return found


def package_edges() -> set:
    """(file relative to the package, imported dotted name) pairs: names
    inside the package lose its prefix, names outside keep theirs."""
    edges = set()
    top = os.path.join(ROOT, PKG)
    for folder, _, files in os.walk(top):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            rel = os.path.relpath(path, top).replace(os.sep, "/")
            package = [PKG] + rel.split("/")[:-1]
            with open(path) as f:
                for mod in imported_modules(f.read(), package):
                    if mod.startswith(PKG + "."):
                        edges.add((rel, mod[len(PKG) + 1:]))
                    elif mod.split(".")[0] in OUTSIDE:
                        edges.add((rel, mod))
    return edges


@pytest.fixture(scope="module")
def edges():
    return package_edges()


def test_the_package_imports_nothing_that_reads_it(edges):
    up = sorted((src, mod) for src, mod in edges
                if mod.split(".")[0] in OUTSIDE)
    assert up == [], f"the package reaches outside itself: {up}"


def _same_import(debt, edge) -> bool:
    """`from pkg.serve import slo` reads as serve and serve.slo, and
    `from pkg.serve.slo import X` as serve.slo and serve.slo.X: one
    import, so one dotted name is a prefix of the other."""
    (src_a, a), (src_b, b) = debt, edge
    return src_a == src_b and (
        a == b or a.startswith(b + ".") or b.startswith(a + "."))


@pytest.mark.parametrize("layer", sorted(FORBIDDEN))
def test_layer_imports_point_down(edges, layer):
    found = {
        (src, mod) for src, mod in edges
        if src.split("/")[0] == layer
        and mod.split(".")[0] in FORBIDDEN[layer]
    }
    owed = {d for d in KNOWN_DEBTS if d[0].split("/")[0] == layer}
    new = sorted(e for e in found
                 if not any(_same_import(d, e) for d in owed))
    assert new == [], f"new upward imports in {layer}/: {new}"
    gone = sorted(d for d in owed
                  if not any(_same_import(d, e) for e in found))
    assert gone == [], f"retired, take out of KNOWN_DEBTS: {gone}"


def test_every_debt_names_a_roadmap_item():
    with open(os.path.join(ROOT, "ROADMAP.md")) as f:
        roadmap = f.read()
    for edge, item in KNOWN_DEBTS.items():
        assert f"**{item}." in roadmap, (edge, item)


@pytest.mark.parametrize("line, package, want", [
    ("from tools.check_stream import audit", [PKG, "serve"],
     "tools.check_stream"),
    ("def f():\n    import perf.lib.flops", [PKG, "utils"],
     "perf.lib.flops"),
    ("from ..train import loop", [PKG, "serve"], PKG + ".train.loop"),
    ("from . import ring", [PKG, "parallel"], PKG + ".parallel.ring"),
], ids=["from-tools", "lazy-perf", "relative-up", "relative-here"])
def test_the_walk_sees_every_form_of_import(line, package, want):
    assert want in imported_modules(line, package)


# ------------------------------------------------------------ one engine
ENGINE_METHODS = {"admit", "step_burst", "release"}


def _serve_tree(name: str) -> ast.Module:
    with open(os.path.join(ROOT, PKG, "serve", name)) as f:
        return ast.parse(f.read())


def _calls(node: ast.AST) -> set:
    return {n.func.id for n in ast.walk(node)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)}


def test_serve_has_one_engine_and_its_three_callers_build_it():
    """PR 43 closed the fork between the engine users started and the one
    the benchmark measured. It stays closed: one class of `serve/` has the
    scheduler's `admit` / `step_burst` / `release`, the package exports
    it, and `serve` (cli.py), `make_router` and the worker construct it."""
    engines = set()
    for name in sorted(os.listdir(os.path.join(ROOT, PKG, "serve"))):
        if name.endswith(".py"):
            engines |= {
                (name, node.name) for node in ast.walk(_serve_tree(name))
                if isinstance(node, ast.ClassDef) and ENGINE_METHODS <= {
                    f.name for f in node.body
                    if isinstance(f, ast.FunctionDef)}}
    assert engines == {("engine.py", "PagedEngine")}
    exported = {
        a.name for node in _serve_tree("__init__.py").body
        if isinstance(node, ast.ImportFrom) and node.module.endswith(
            ".serve.engine") for a in node.names}
    assert "PagedEngine" in exported
    assert not {n for n in exported if n.endswith("Engine")} - {"PagedEngine"}
    (make_router,) = [n for n in _serve_tree("router.py").body
                      if isinstance(n, ast.FunctionDef)
                      and n.name == "make_router"]
    (worker,) = [n for n in _serve_tree("worker.py").body
                 if isinstance(n, ast.ClassDef) and n.name == "WorkerServer"]
    for where, node in (("cli.py", _serve_tree("cli.py")),
                        ("make_router", make_router),
                        ("WorkerServer", worker)):
        assert "PagedEngine" in _calls(node), where
