"""docs/env_var.md against the code, and the names PR 31 removed.

A name is *read* when it stands alone as a string literal in a Python file
under ``mxnet_tpu/`` or ``tools/`` (``os.environ.get("MXNET_X")``, a helper's
argument); a mention in a docstring or a comment is not a read.
"""
import ast
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"MXNET_[A-Z0-9_]+")


def _files(*dirs, ext):
    for d in dirs:
        for dp, _, fns in os.walk(os.path.join(REPO, d)):
            for fn in fns:
                if fn.endswith(ext):
                    yield os.path.join(dp, fn)


def _names_read():
    read = {}
    for path in _files("mxnet_tpu", "tools", ext=".py"):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                    and NAME.fullmatch(node.value):
                read.setdefault(node.value, os.path.relpath(path, REPO))
    return read


def _names_documented():
    """The names in the first cell of every table row of docs/env_var.md
    (prose, such as the reference's ``MXNET_EXEC_BULK_EXEC_*``, is no row)."""
    rows = set()
    with open(os.path.join(REPO, "docs", "env_var.md")) as f:
        for line in f:
            if line.startswith("| `"):
                rows.update(re.findall(r"`(MXNET_[A-Z0-9_]+)`",
                                       line.split("|")[1]))
    return rows


def test_env_var_doc_has_a_row_for_every_name_read_and_no_other():
    read, rows = _names_read(), _names_documented()
    assert len(read) > 50 and len(rows) > 50  # the scan found the tables
    missing = {n: read[n] for n in sorted(set(read) - rows)}
    assert not missing, "read, but no row in docs/env_var.md: %s" % missing
    stale = sorted(rows - set(read))
    assert not stale, "a row in docs/env_var.md, but nothing reads: %s" % stale


# the capture and fuse tiers (PR 31): classes, gates and environment names
REMOVED = ("CapturedSequence", "FusedSequence", "FuseOp", "CapturedTrainStep",
           "MXNET_ENGINE_CAPTURE", "MXNET_ENGINE_CAPTURE_WARMUP",
           "MXNET_ENGINE_FUSE", "MXNET_DECODE_CAPTURE")


def test_removed_tier_names_are_left_nowhere():
    pat = re.compile("|".join(REMOVED))
    left = []
    for path in _files("mxnet_tpu", "docs", "tools", "examples", "ci",
                       ext=(".py", ".md", ".sh", ".json", ".txt")):
        with open(path, errors="replace") as f:
            for i, line in enumerate(f, 1):
                if pat.search(line):
                    left.append("%s:%d" % (os.path.relpath(path, REPO), i))
    assert not left, left
