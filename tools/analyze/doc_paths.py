"""README path cross-check pass (ISSUE 32): a file the README names
exists.

The README names source files in backticks (`ops/compact.py`,
`tools/fsck.py`) and in command lines (``python tools/pressure_test.py
--qps 500``). A file that was deleted or moved leaves such a mention
pointing at nothing — a command a reader cannot run. Checked, one way
only (the README need not name every file):

  * a word starting with ``tools/``, ``pegasus_tpu/``, ``tests/`` or
    ``benchmarks/`` exists under the root (a trailing ``/`` names a
    directory);
  * any other word with a ``/`` that ends in ``.py`` / ``.c`` / ``.cpp``
    is the README's shorthand for a package module and exists under the
    root or under ``pegasus_tpu/``;
  * a bare ``name.py`` exists at the root or, shorthand after a full
    path, under ``tools/``.

Words come from backticked spans and from fenced code blocks, split on
whitespace, cut at ``::`` (a test id) and stripped of punctuation. A
word with a placeholder or a glob (``<dir>/x.py``, ``tools/check_*.py``)
is not a path and is skipped.
"""

import re

from . import Finding, Repo, register

_SPAN_RE = re.compile(r"```[^\n]*\n(.*?)```|`([^`\n]+)`", re.DOTALL)
_ROOTED = ("tools/", "pegasus_tpu/", "tests/", "benchmarks/")
_SOURCE_RE = re.compile(r"[\w./-]+\.(?:py|c|cpp)")
_NOT_A_PATH = set("<>*{}$[]")


def readme_paths(repo: Repo) -> list:
    """Path-like words of the README, in order, without repeats."""
    words = []
    for block, span in _SPAN_RE.findall(repo.readme):
        for w in (block or span).split():
            w = w.split("::")[0].strip(".,;:()'\"")
            if not w or _NOT_A_PATH & set(w) or w in words:
                continue
            if w.startswith(_ROOTED) or _SOURCE_RE.fullmatch(w) and (
                    "/" in w or w.endswith(".py")):
                words.append(w)
    return words


def _where(word: str) -> list:
    """The places, relative to the root, where `word` may lie."""
    if word.startswith(_ROOTED):
        return [word]
    if "/" in word:
        return [word, "pegasus_tpu/" + word]
    return [word, "tools/" + word]


@register("doc_paths")
def run(repo: Repo = None) -> list:
    repo = repo or Repo()
    out = []
    for word in readme_paths(repo):
        if not any((repo.root / rel).exists() for rel in _where(word)):
            out.append(Finding(
                "doc_paths", "", 0,
                f"README.md names {word!r}, which does not exist — "
                f"correct the mention or restore the file",
                key=f"missing:{word}"))
    return out
