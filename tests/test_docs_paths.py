"""The documents point at things that exist.

One case per document (README.md and every docs/*.md): each back-ticked
path into the tree names a file or directory that is there, and each
`python -m <module>` / `python <script>` the reader is told to run
resolves. A deleted script that a document still sends the reader to
fails here, not in the reader's shell."""

import functools
import importlib.util
import os
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
DOCS = [ROOT / "README.md"] + sorted((ROOT / "docs").glob("*.md"))

# top-level directories a back-ticked path may start with
_TOP_DIRS = ("openr_tpu", "chipbench", "tests", "docs", "native")
# a token with one of these is a pattern or a placeholder, not a path
_PLACEHOLDER = re.compile(r"[<>*{}$\[\]|]|NN|\.\.\.")
_FILE_SUFFIXES = (
    ".py", ".md", ".json", ".jsonl", ".txt", ".cpp", ".h", ".c", ".so",
)
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_PYTHON_CMD = re.compile(
    r"\bpython3?\s+(?:-m\s+([A-Za-z_][\w.]*)|([\w./-]+\.py)\b)"
)


@functools.lru_cache(maxsize=None)
def _tree():
    """Every file and directory of the checkout, as posix paths from
    its root; scratch and hidden directories are not walked."""
    skip = {"__pycache__", "_chipcheck", "_chipscripts", "chiprun_out"}
    out = []
    for here, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in skip and d[0] != "."]
        rel = Path(here).relative_to(ROOT)
        out += [(rel / name).as_posix() for name in dirs + files]
    return out


@functools.lru_cache(maxsize=None)
def _basenames():
    return {p.rsplit("/", 1)[-1] for p in _tree()}


def _clean(token: str) -> str:
    """`path/file.py:12-40`, `path/file.py::test_x`, `path/file.py,` ->
    `path/file.py`."""
    token = token.strip("()\"',;")
    token = re.split(r"::|:(?=[\d`A-Za-z_])", token, maxsplit=1)[0]
    return token.rstrip(".,:;)")


def _exists(path: str) -> bool:
    path = path.rstrip("/")
    return (
        (ROOT / path).exists()
        # the documents also write paths from the package's root
        # (`solver/tpu.py`) and from native/'s
        or (ROOT / "openr_tpu" / path).exists()
        or any(p.endswith("/" + path) for p in _tree())
    )


def _missing_paths(text: str):
    missing = []
    for span in _BACKTICKED.findall(text):
        for raw in span.split():
            token = _clean(raw)
            if not token or _PLACEHOLDER.search(token):
                continue
            first = token.split("/", 1)[0]
            if "/" in token and first in _TOP_DIRS:
                # a path into the tree, file or directory
                if not _exists(token):
                    missing.append(token)
            elif "/" in token and token.endswith(_FILE_SUFFIXES):
                # a path written from somewhere inside the tree;
                # `openr/...` is the upstream project's tree, not ours
                if first != "openr" and not token.startswith("/"):
                    if not _exists(token):
                        missing.append(token)
            elif (
                "/" not in token
                and token.endswith((".py", ".md"))
                and re.fullmatch(r"[\w.-]+", token)
            ):
                # a bare script or document name
                if token not in _basenames():
                    missing.append(token)
    return missing


def _missing_commands(text: str):
    missing = []
    for module, script in _PYTHON_CMD.findall(text):
        if module:
            try:
                found = importlib.util.find_spec(module) is not None
            except ModuleNotFoundError:
                found = False
            if not found:
                missing.append(f"python -m {module}")
        elif not (ROOT / script).exists():
            missing.append(f"python {script}")
    return missing


@pytest.mark.parametrize("doc", DOCS, ids=lambda p: p.name)
def test_document_points_at_what_exists(doc):
    text = doc.read_text()
    assert _missing_paths(text) == []
    assert _missing_commands(text) == []


def test_checker_sees_a_missing_script_and_module():
    text = "run `python no_such_script.py` or `python -m no_such_pkg.mod`"
    assert _missing_paths(
        "see `no_such_script.py`, `tests/no_such_dir/` and `ctrl/nope.py:12`"
    ) == ["no_such_script.py", "tests/no_such_dir/", "ctrl/nope.py"]
    assert _missing_commands(text) == [
        "python no_such_script.py",
        "python -m no_such_pkg.mod",
    ]
