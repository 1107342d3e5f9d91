"""The documents describe the tree that is there.

Three checks, each over the documents a newcomer is sent to:

* a back-ticked path (`fedml_tpu/obs/slo.py`, `tools/README.md`) names a
  file or directory that exists;
* a back-ticked `path.py::name` names a definition in that file (PERF.md's
  layer table and ROADMAP's open items are written this way; a history
  names what is gone in plain words);
* a command in a fenced block still runs as far as its arguments: a
  `python -m fedml_tpu.cli ...` line parses with the real parser, and a
  `python tools/x.py`, `python chip_smoke.py` or `python3 -m fedbench.run`
  names something that exists.

Nothing here greps for a phrase: a document may say what it likes, as
long as what it points at is there."""
import ast
import importlib.util
import os
import re
import shlex

import pytest

from repo_tree import REPO

PYPROJECT = os.path.join(REPO, "pyproject.toml")
SKILL = ".claude/skills/verify/SKILL.md"

SUFFIXES = (".py", ".md", ".json", ".jsonl", ".sh", ".toml", ".cpp")
# named by the documents and no part of a checkout: FedML's own README,
# and what a run leaves in its output directory
NOT_OURS = ("benchmark/README.md", "program_trace.json", "scope_map.json",
            "phase_trace.json", "phase_map.json", "kernel_trace.json",
            "clock_offsets.json", "critical_path.json", "merged.chrome.json")
TOP_LEVEL = {name for name in os.listdir(REPO)
             if os.path.isdir(os.path.join(REPO, name))}
TICKED = re.compile(r"`([^`\n]+)`")
PATH_LIKE = re.compile(r"^[\w./-]+$")


def _read(doc: str) -> str:
    with open(os.path.join(REPO, doc)) as f:
        return f.read()


def _resolve(path: str, doc: str):
    """The file or directory `path` names, or None.  A path is written
    from the repo's root, from the package (`parallel/engine.py`) or from
    the document's own directory (tools/README.md lists its neighbours)."""
    for root in ("", "fedml_tpu", os.path.dirname(doc)):
        full = os.path.join(REPO, root, path)
        if os.path.exists(full):
            return full
    return None


def _cited_paths(text: str):
    """Back-ticked tokens that are written as a path of this tree: they
    have a directory or a source suffix, and no placeholder, glob, option
    or space in them.  `a/b.py::name` and `a/b.py:12` cite `a/b.py`."""
    for token in TICKED.findall(text):
        path = re.split(r"::|:\d", token, maxsplit=1)[0].rstrip(".,;")
        if not PATH_LIKE.match(path) or path.startswith(("-", "/", ".")) or (
                ".." in path.split("/")):      # nor anything outside it
            continue
        if any(mark in path for mark in NOT_OURS):
            continue
        if path.endswith(SUFFIXES) or (
                "/" in path and path.split("/")[0] in TOP_LEVEL):
            yield path


@pytest.mark.parametrize("doc", [
    "README.md", "PERF.md", "tools/README.md", "benchmarks/README.md",
    SKILL, "MIGRATION.md", "PARITY.md"])
def test_cited_files_exist(doc):
    cited = sorted(set(_cited_paths(_read(doc))))
    assert len(cited) >= 3, f"{doc}: the reader found only {cited}"
    missing = [p for p in cited if _resolve(p, doc) is None]
    assert not missing, f"{doc} cites what is not in the tree: {missing}"


def _defined_names(path: str) -> set:
    """Every function, class and assigned name in the file, at any depth,
    plus `Class.method` for methods."""
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()

    def visit(node, prefix=""):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                names.update({child.name, prefix + child.name})
                visit(child, prefix + child.name + "."
                      if isinstance(child, ast.ClassDef) else prefix)
            else:
                if isinstance(child, (ast.Assign, ast.AnnAssign)):
                    targets = (child.targets if isinstance(child, ast.Assign)
                               else [child.target])
                    for t in targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                names.add(n.id)
                            elif isinstance(n, ast.Attribute):
                                names.add(n.attr)
                visit(child, prefix)
    visit(tree)
    return names


@pytest.mark.parametrize("doc", ["PERF.md", "ROADMAP.md"])
def test_cited_symbols_exist(doc):
    cited = sorted({(m.group(1), m.group(2)) for m in re.finditer(
        r"`([\w./-]+\.py)::([\w./]+)[^`]*`", _read(doc))})
    assert len(cited) >= 3, f"{doc}: the reader found only {cited}"
    missing = []
    for path, symbols in cited:
        full = _resolve(path, doc)
        if full is None:
            missing.append(f"{path} (no such file)")
            continue
        defined = _defined_names(full)
        # `engine.py::take_cohort/cohort_slices` cites two names
        missing += [f"{path}::{s}" for s in symbols.split("/")
                    if s and s not in defined]
    assert not missing, f"{doc} cites what is not defined: {missing}"


# -- commands in fenced blocks ------------------------------------------------

CLI = re.compile(r"\bpython3? -m fedml_tpu(?:\.cli)? (.*)")
MODULE = re.compile(r"\bpython3? -m ([\w.]+)")
SCRIPT = re.compile(r"\bpython3? ([\w./-]+\.py)\b")


def _fenced_lines(text: str):
    """The lines of the fenced blocks, continuations joined."""
    for block in re.findall(r"```(?:bash|sh|shell)?\n(.*?)```", text, re.S):
        yield from re.sub(r"\\\n", " ", block).splitlines()


def _wrong_with(line: str, parser) -> str:
    """'' if every command the line holds is good, else what is wrong."""
    for module in MODULE.findall(line):
        if importlib.util.find_spec(module) is None:
            return f"no module {module}"
    for script in SCRIPT.findall(line):
        # a skill may spell the checkout's path out; never look outside it
        rel = script.removeprefix("/root/repo/")
        if os.path.isabs(rel) or not os.path.exists(os.path.join(REPO, rel)):
            return f"no file {script} in the checkout"
    cli = CLI.search(line)
    if cli:
        # up to the first pipe, redirection, `&` or comment; `$common`
        # and `...` stand for flags the line does not spell out
        args = [a for a in shlex.split(
            re.split(r"\s[|>&#]|;", cli.group(1) + " ")[0])
            if not a.startswith("$") and a != "..."]
        try:
            unknown = parser.parse_known_args(args)[1]
        except SystemExit:
            return f"the parser refuses {args}"
        if any(u.startswith("--") for u in unknown):
            return f"unknown flags {unknown}"
    return ""


@pytest.mark.parametrize("doc, at_least", [("README.md", 10), (SKILL, 8)])
def test_documented_commands_parse(doc, at_least):
    from fedml_tpu.cli import build_parser
    parser = build_parser()
    lines = list(_fenced_lines(_read(doc)))
    n_cli = sum(1 for line in lines if CLI.search(line))
    assert n_cli >= at_least, (
        f"{doc}: the reader found only {n_cli} fedml_tpu.cli commands")
    wrong = [f"{line.strip()}: {why}" for line in lines
             for why in [_wrong_with(line, parser)] if why]
    assert not wrong, "\n".join(wrong)


# -- pyproject ----------------------------------------------------------------

def _addopts() -> str:
    text = open(PYPROJECT).read()
    try:
        import tomllib
        opts = (tomllib.loads(text).get("tool", {}).get("pytest", {})
                .get("ini_options", {}).get("addopts", ""))
    except ModuleNotFoundError:               # python 3.10: regex fallback
        m = re.search(r'^addopts\s*=\s*"(.*)"\s*$', text, re.M)
        opts = m.group(1) if m else ""
    if isinstance(opts, list):
        opts = " ".join(opts)
    return opts


def test_addopts_never_hardcodes_xdist():
    """An unconditional `-n auto` in addopts once killed EVERY pytest run
    in an image without pytest-xdist ("unrecognized arguments: -n" before
    collecting a single test).  PR 1 removed it, pyproject's comment says
    so, and this keeps it removed: parallelism is the caller's choice
    (the driver passes `-p xdist -n 6 --dist loadfile`)."""
    opts = _addopts()
    tokens = opts.split()
    assert "-n" not in tokens and "--numprocesses" not in tokens, (
        f"pyproject addopts={opts!r} reintroduces pytest-xdist flags: "
        "xdist is absent in the CI image and this kills every pytest "
        "run with 'unrecognized arguments: -n' (see PR-1 history)")
    assert "--dist" not in tokens and "--maxprocesses" not in tokens, (
        f"addopts={opts!r} carries xdist-only companions that fail "
        "without the plugin")
