"""The repo's files, for the tests that read the tree itself
(test_one_instrument, test_option_census, test_docs_match_tree).  Not a
test file."""
import functools
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# what building, testing and running leave behind (.gitignore)
_NOT_SOURCE = ("__pycache__", "chiprun_out", "runs")


@functools.lru_cache(maxsize=None)
def source_files(*tops: str) -> tuple:
    """Repo-relative paths of the files under `tops` (files or
    directories; the whole checkout without), hidden directories and
    what a run leaves behind left out.  The disk is read, not git's
    index: a checkout need not carry `.git`."""
    files = []
    for top in tops or (".",):
        path = os.path.join(REPO, top)
        if os.path.isfile(path):
            files.append(top)
        for d, dirs, names in os.walk(path):
            dirs[:] = [x for x in dirs if not x.startswith(".")
                       and x not in _NOT_SOURCE]
            files += [os.path.relpath(os.path.join(d, n), REPO)
                      for n in names if not n.endswith((".pyc", ".so"))]
    return tuple(files)
