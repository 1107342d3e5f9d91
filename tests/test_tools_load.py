"""Every script under tools/ still loads against the tree it lives in.

Four of the nine have no other test, and a script is run rarely: the
one way it rots unseen is an import of something a later PR moved or
deleted.  Each file is imported in-process (its `__main__` guard keeps
it from running), and every import statement in it — the lazy ones
inside functions too, which importing the file never executes — must
resolve to a module, and each imported name to an attribute of it."""
import ast
import glob
import importlib
import importlib.util
import os
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = sorted(os.path.basename(p)
               for p in glob.glob(os.path.join(REPO, "tools", "*.py")))


def _load(name: str):
    path = os.path.join(REPO, "tools", name)
    spec = importlib.util.spec_from_file_location(
        "_tool_under_test_" + name[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module, path


def test_every_tool_is_listed():
    assert len(TOOLS) >= 9, TOOLS


@pytest.mark.parametrize("tool", TOOLS)
def test_tool_loads(tool, monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    monkeypatch.syspath_prepend(os.path.join(REPO, "tools"))
    argv, threads = list(sys.argv), threading.active_count()
    module, path = _load(tool)
    assert callable(getattr(module, "main", None)), f"{tool}: no main()"
    assert sys.argv == argv, f"{tool} parsed or changed argv on import"
    assert threading.active_count() == threads, (
        f"{tool} started a thread on import")
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                importlib.import_module(alias.name)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            mod = importlib.import_module(node.module)
            for alias in node.names:
                if not hasattr(mod, alias.name):
                    importlib.import_module(f"{node.module}.{alias.name}")
