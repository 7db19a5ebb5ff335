"""The README and docs/ cannot name a file of this repo that is not there.

A deleted tool or script otherwise lives on in the documents that send a
newcomer to it (PR 30: the README's "how to measure" was a script two
generations old). The reference repo's files are out of scope: the
documents write them with a ``src/`` or ``/root/reference/`` prefix.
"""

import glob
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: directories a repo-relative path in a document starts with
PREFIXES = ("tools/", "tests/", "benchmark/", "docs/",
            "pytorch_distributed_nn_tpu/")
_FENCE = re.compile(r"```.*?```", re.S)
_SPAN = re.compile(r"`([^`\n]+)`")
_BARE_PY = re.compile(r"[\w\-]+\.py")
_PATTERN_CHARS = set("*<>{}[]$…")


def _code_words(text):
    """Words of the fenced blocks and the inline code spans."""
    for block in _FENCE.findall(text):
        yield from block.strip("`").split()
    for span in _SPAN.findall(_FENCE.sub("", text)):
        yield from span.split()


def _named_paths(doc):
    with open(doc) as f:
        text = f.read()
    for word in _code_words(text):
        # `tests/test_x.py::TestY`, `trainer.py:1509`, `(tools/lint.sh),`
        path = re.split(r"[:#]", word.strip("\"'(),;|"))[0].rstrip(".")
        if _PATTERN_CHARS & set(path):
            continue  # a glob or a placeholder, not one file
        if path.startswith(PREFIXES) or _BARE_PY.fullmatch(path):
            yield path


def test_documents_name_only_files_that_exist():
    basenames = set()
    for _, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "chiprun_out"]
        basenames.update(files)
    docs = [os.path.join(ROOT, "README.md")] + sorted(
        glob.glob(os.path.join(ROOT, "docs", "*.md")))
    assert len(docs) > 5
    missing = []
    for doc in docs:
        for path in _named_paths(doc):
            # a bare `name.py` is a module named without its directory
            there = (path in basenames if "/" not in path
                     else os.path.exists(os.path.join(ROOT, path)))
            if not there:
                missing.append(f"{os.path.relpath(doc, ROOT)}: {path}")
    assert not missing, "documents name files that are not there:\n" + (
        "\n".join(missing))
