"""A ratchet on the repo's independently settable values (ROADMAP D6).

Each option doubles the configurations that tests and the benchmark
would have to cover, so a PR counts them before and after.  The
ceilings below are the counts of the tree as it is: a PR that removes an
option lowers the constant, and a PR that needs a new one raises it in
the same diff, where a reviewer sees it.  Every count is taken from the
object the program uses (the built parser, the signatures, the parsed
sources), not from a file's text."""
import ast
import inspect
import os
import re

import pytest

from repo_tree import REPO, source_files

ENV_NAME = re.compile(r"FEDML_[A-Z0-9]+(_[A-Z0-9]+)*")
# calls that put a name INTO an environment do not read an option
ENV_WRITERS = {"setenv", "delenv"}


def _cli_flags() -> int:
    from fedml_tpu.cli import build_parser
    return sum(1 for a in build_parser()._actions if a.dest != "help")


def _env_names() -> set:
    """Every FEDML_* name that a source file holds as a whole string
    constant — an `os.environ.get("X")`, an `ENV_X = "X"`, an
    `env["X"] = ...` — and so can be set to change what the code does.
    Names a test only plants (`monkeypatch.setenv`) are not options."""
    names = set()
    for rel in source_files():
        if not rel.endswith(".py"):
            continue
        with open(os.path.join(REPO, rel)) as f:
            tree = ast.parse(f.read())
        planted = {id(arg) for call in ast.walk(tree)
                   if isinstance(call, ast.Call)
                   and isinstance(call.func, ast.Attribute)
                   and call.func.attr in ENV_WRITERS
                   for arg in call.args}
        names |= {n.value for n in ast.walk(tree)
                  if isinstance(n, ast.Constant) and isinstance(n.value, str)
                  and ENV_NAME.fullmatch(n.value) and id(n) not in planted}
    return names


def _keywords(cls) -> int:
    return sum(1 for p in inspect.signature(cls.__init__).parameters.values()
               if p.default is not inspect.Parameter.empty)


def _engine_keywords() -> int:
    from fedml_tpu.parallel import MeshFedAvgEngine
    return _keywords(MeshFedAvgEngine)


def _trainer_keywords() -> int:
    from fedml_tpu.core.trainer import ClientTrainer
    return _keywords(ClientTrainer)


@pytest.mark.parametrize("surface, count, ceiling", [
    pytest.param(name, count, ceiling, id=name) for name, count, ceiling in (
        ("cli_flags", _cli_flags, 152),
        ("fedml_env_names", lambda: len(_env_names()), 15),
        ("mesh_engine_keywords", _engine_keywords, 10),
        ("client_trainer_keywords", _trainer_keywords, 13))])
def test_option_count_does_not_grow(surface, count, ceiling):
    n = count()
    assert n <= ceiling, (
        f"{surface}: {n} > {ceiling} — an option was added"
        + (f": {sorted(_env_names())}" if surface == "fedml_env_names"
           else ""))
    assert n == ceiling, (
        f"{surface}: {n} < {ceiling} — an option went: lower the ceiling "
        f"in this test to {n}, so that it cannot come back unseen")
