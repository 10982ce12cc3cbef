"""The documents name only what the tree has.

One case a document. A case fails when its document names, inside
backticks or on a ``python ...`` command line, a path of the repository
that does not exist, a ``tests/...::test_name`` that the file does not
define, a ``python -m rafiki_tpu.<module>`` that is no module, or a
``python -m rafiki_tpu <sub>`` that ``rafiki_tpu/__main__.py`` does not
register. Pure text: nothing of the package is imported.
"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "BASELINE.md", ".claude/skills/verify/SKILL.md"] \
    + sorted(os.path.relpath(p, ROOT)
             for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

#: A token that begins with one of these is a path of the repository.
TOP_DIRS = ("rafiki_tpu/", "tests/", "docs/", "benchmarks/", "examples/",
            "scripts/", "dockerfiles/")
#: A bare ``name.py`` / ``name.json`` / ``name.md`` is a file at the top
#: level, or a file the sentence has already placed by its directory
#: (``model/jax_model.py`` ... ``jax_model.py``): any file of that name.
BARE_FILE = re.compile(r"^[\w.-]+\.(py|json|md)$")
#: Named in the documents, written by the reader (the verify skill's
#: scratch script) or at run time (the capacity engine's table), never
#: part of the tree.
NOT_OF_THE_REPO = {"drive.py", "periodicity.json"}

_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_SPAN = re.compile(r"`([^`]+)`")  # may run over a line's end
_COMMAND = re.compile(r"^\s*(?:[A-Z_]+=\S+\s+)*(?:timeout \d+\s+)?"
                      r"(python3?\s.*)$", re.M)
_SUBCOMMAND = re.compile(r"python3? -m rafiki_tpu\s+([a-z][\w-]*)")
_MODULE = re.compile(r"python3? -m (rafiki_tpu(?:\.\w+)+)")


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set(os.listdir(ROOT))
    for top in TOP_DIRS:
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return names


@functools.lru_cache(maxsize=None)
def _registered_subcommands():
    with open(os.path.join(ROOT, "rafiki_tpu", "__main__.py")) as f:
        return set(re.findall(r"add_parser\(\s*\"([\w-]+)\"", f.read()))


def _named(text):
    """Every whitespace-separated token of the backtick spans and the
    ``python`` command lines of ``text``, trimmed of punctuation."""
    fenced = "\n".join(_FENCE.findall(text))
    pieces = _SPAN.findall(_FENCE.sub("", text))
    pieces += _COMMAND.findall(fenced)
    for piece in pieces:
        for token in piece.split():
            yield token.strip("()[]{},;\"'").rstrip(".:")


def _problems(text, basenames, subcommands):
    out = []
    for token in _named(text):
        if any(c in token for c in "<>$…") or token.startswith(("http", "-")):
            continue  # a placeholder, a URL, a flag
        path, _, test = token.partition("::")
        path = re.sub(r":\d+(-\d+)?$", "", path)  # path:line
        if path.startswith(TOP_DIRS):
            full = os.path.join(ROOT, path)
            if not (glob.glob(full) if "*" in path else os.path.exists(full)):
                out.append(f"no such path: {token}")
            elif test and path.endswith(".py"):
                with open(full) as f:
                    if not re.search(rf"def {re.escape(test)}\b", f.read()):
                        out.append(f"no such test: {token}")
        elif BARE_FILE.match(path) and path not in NOT_OF_THE_REPO \
                and path not in basenames:
            out.append(f"no such file: {token}")
    for module in _MODULE.findall(text):
        base = os.path.join(ROOT, *module.split("."))
        if not (os.path.exists(base + ".py")
                or os.path.exists(os.path.join(base, "__main__.py"))):
            out.append(f"no such module: python -m {module}")
    for sub in _SUBCOMMAND.findall(text):
        if sub not in subcommands:
            out.append(f"no such subcommand: python -m rafiki_tpu {sub}")
    return sorted(set(out))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    assert _problems(text, _basenames(), _registered_subcommands()) == []


def test_checker_catches_a_deleted_file_and_an_unknown_subcommand():
    text = ("Run `python gone.py --config x`, see `tests/test_gone.py`, "
            "`tests/test_docs.py::test_nothing`, `docs/serving.md:12`,\n"
            "    python -m rafiki_tpu fly --now\n"
            "    python -m rafiki_tpu.nothing\n")
    assert _problems(text, {"README.md"}, {"serve"}) == [
        "no such file: gone.py",
        "no such module: python -m rafiki_tpu.nothing",
        "no such path: tests/test_gone.py",
        "no such subcommand: python -m rafiki_tpu fly",
        "no such test: tests/test_docs.py::test_nothing",
    ]
