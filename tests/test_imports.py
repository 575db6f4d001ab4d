"""Compat-shim and layering guarantees for the three-layer core split.

The PR that decomposed `core/index_io.py` into `core/adc.py` (numerics),
`core/traversal.py` (beam engine) and a slimmed `core/index_io.py`
(format + lifecycle) promises external users of the old monolith that
every pre-split import path keeps resolving — and that the new layering
introduced no import cycles inside `repro.core`.
"""
import ast
import importlib
import os
import pkgutil

import pytest

_OLD_MONOLITH_SYMBOLS = [
    # ADC numerics (now core.adc)
    "np_build_lut", "np_build_lut_batch", "np_adc",
    "np_quantize_lut", "np_adc_int8", "np_host_lut_int8",
    # engine surface (now core.traversal)
    "SearchStats", "recall_at",
    # never moved
    "HostIndex", "write_index",
]


def test_index_io_reexports_every_monolith_symbol():
    """`from repro.core.index_io import np_* / SearchStats / ...` — the
    pre-split import paths — must all still resolve."""
    index_io = importlib.import_module("repro.core.index_io")
    for name in _OLD_MONOLITH_SYMBOLS:
        assert hasattr(index_io, name), f"index_io lost {name}"


def test_reexports_are_the_same_objects():
    """The shim re-exports the REAL objects, not copies: isinstance checks
    and monkeypatching through either path stay coherent."""
    from repro.core import adc, index_io, traversal
    for name in ("np_build_lut", "np_build_lut_batch", "np_adc",
                 "np_quantize_lut", "np_adc_int8", "np_host_lut_int8"):
        assert getattr(index_io, name) is getattr(adc, name), name
    assert index_io.SearchStats is traversal.SearchStats
    assert index_io.recall_at is traversal.recall_at


def test_dynamic_reexports_survive():
    """core.dynamic's public surface (monolith era) still imports."""
    from repro.core.dynamic import np_adc, np_build_lut  # noqa: F401
    from repro.core.dynamic import SearchStats  # noqa: F401


def _core_import_graph():
    """Module-level intra-package import edges of repro.core, via ast (no
    execution): module -> set of repro.core siblings it imports."""
    import repro.core as core_pkg
    pkg_dir = os.path.dirname(core_pkg.__file__)
    names = {m.name for m in pkgutil.iter_modules([pkg_dir])}
    graph = {}
    for name in names:
        with open(os.path.join(pkg_dir, f"{name}.py")) as f:
            tree = ast.parse(f.read())
        deps = set()
        for node in ast.walk(tree):
            # only MODULE-LEVEL imports create import-time cycles; imports
            # inside functions are lazy and explicitly allowed
            if not isinstance(node, (ast.Import, ast.ImportFrom)):
                continue
            if any(not isinstance(p, ast.Module)
                   for p in _parents(tree, node)):
                continue
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif node.module:
                mods = [node.module]
            for mod in mods:
                parts = mod.split(".")
                if parts[:2] == ["repro", "core"] and len(parts) > 2 \
                        and parts[2] in names:
                    deps.add(parts[2])
        graph[name] = deps - {name}
    return graph


def _parents(tree, target):
    """Chain of ancestor nodes of `target` inside `tree`."""
    chain = []

    def walk(node, path):
        if node is target:
            chain.extend(path)
            return True
        for child in ast.iter_child_nodes(node):
            if walk(child, path + [node]):
                return True
        return False

    walk(tree, [])
    return chain


def test_core_has_no_import_cycles():
    """DFS over the module-level import graph of repro.core: any cycle
    (e.g. index_io <-> traversal importing each other eagerly) would make
    the split's import order fragile for external users."""
    graph = _core_import_graph()
    # sanity: the expected layering edges exist at all
    assert "adc" in graph["traversal"]
    assert {"adc", "traversal"} <= graph["index_io"]

    WHITE, GREY, BLACK = 0, 1, 2
    color = {n: WHITE for n in graph}
    stack_trace = []

    def dfs(n):
        color[n] = GREY
        stack_trace.append(n)
        for d in sorted(graph.get(n, ())):
            if color[d] == GREY:
                cycle = stack_trace[stack_trace.index(d):] + [d]
                pytest.fail("import cycle in repro.core: "
                            + " -> ".join(cycle))
            if color[d] == WHITE:
                dfs(d)
        stack_trace.pop()
        color[n] = BLACK

    for n in sorted(graph):
        if color[n] == WHITE:
            dfs(n)


def test_every_core_module_imports_cleanly():
    """Each repro.core module imports on its own (no hidden ordering
    dependence introduced by the split)."""
    import repro.core as core_pkg
    pkg_dir = os.path.dirname(core_pkg.__file__)
    for m in pkgutil.iter_modules([pkg_dir]):
        importlib.import_module(f"repro.core.{m.name}")


# the seed's LM/GNN/recsys training stack: the retriever imports none of it
TRAINING_STACK = ("repro.models", "repro.distributed", "repro.optim",
                  "repro.checkpoint", "repro.configs", "repro.launch.train",
                  "repro.data.pipeline")


def _in_training_stack(mod):
    return any(mod == t or mod.startswith(t + ".") for t in TRAINING_STACK)


def _src_modules():
    """(dotted name, path) of every module under src/repro."""
    import repro.core as core_pkg
    src = os.path.dirname(os.path.dirname(os.path.dirname(core_pkg.__file__)))
    for dirpath, _, files in os.walk(os.path.join(src, "repro")):
        for f in sorted(files):
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                parts = os.path.relpath(path, src)[:-3].split(os.sep)
                if parts[-1] == "__init__":
                    parts = parts[:-1]
                yield ".".join(parts), path


def test_retriever_imports_nothing_of_the_training_stack():
    """No module outside the training stack imports one inside it, at any
    nesting (imports inside functions too), by `import a.b` or by
    `from a import b`."""
    mods = dict(_src_modules())
    assert "repro.core.device_index" in mods and "repro.models.moe" in mods
    bad = []
    for name, path in sorted(mods.items()):
        if _in_training_stack(name):
            continue
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                targets = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                    and node.module:
                # `from repro import configs` names the module in `a.name`
                targets = [f"{node.module}.{a.name}" for a in node.names]
            else:
                continue
            bad += [f"{name}:{node.lineno} imports {t}" for t in targets
                    if _in_training_stack(t)]
    assert not bad, "\n".join(bad)


def test_retriever_import_loads_no_training_module():
    """A fresh interpreter that imports the retriever's entry modules holds
    no training-stack module afterwards: package `__init__` side effects
    count too."""
    import subprocess
    import sys
    import repro.core as core_pkg
    src = os.path.dirname(os.path.dirname(os.path.dirname(core_pkg.__file__)))
    script = (
        "import sys\n"
        "import repro.core.device_index, repro.serving.engine, "
        "repro.core.build, repro.launch.build_index, "
        "repro.core.index_config\n"
        f"stack = {TRAINING_STACK!r}\n"
        "print('\\n'.join(m for m in sorted(sys.modules) if any("
        "m == t or m.startswith(t + '.') for t in stack)))\n")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == "", r.stdout
