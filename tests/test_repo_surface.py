"""The repo's surface points only at what exists: every Makefile target
runs files and modules that are there, and the documents name files that
are there. Both are read as text; nothing is built or run.
"""
import importlib.util
import itertools
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a token holding one of these is a pattern or a placeholder, not a path
_PATTERN_CHARS = re.compile(r"[<*$]")


def _read(relpath):
    with open(os.path.join(REPO_ROOT, relpath), encoding="utf-8") as fh:
        return fh.read()


# -- Makefile -----------------------------------------------------------------

def _parse_makefile(text):
    """{target: (prerequisites, recipe)}; `.PHONY` is kept as a rule so its
    names are checked like any prerequisite."""
    rules, current = {}, None
    for line in text.replace("\\\n", " ").splitlines():
        if line.startswith("\t"):
            if current is not None:
                rules[current][1].append(line.strip())
            continue
        m = re.match(r"^([.A-Za-z0-9_-]+):(?!=)(.*)$", line)
        if m is None:
            if line.strip() and not line.startswith("#"):
                current = None  # a variable assignment ends the rule
            continue
        current = m.group(1)
        prereqs, recipe = rules.setdefault(current, ([], []))
        if "=" not in m.group(2):  # `verify: SHELL := ...` names no file
            prereqs.extend(m.group(2).split())
    return rules


_PYTHON = r"\bpython3?(?: -u)? "  # an interpreter call in a recipe
_RULES = _parse_makefile(_read("Makefile"))
_PHONY = _RULES.pop(".PHONY", ([], []))[0]
# a name `.PHONY` lists but no rule defines still gets its (failing) case
_TARGETS = sorted(set(_RULES) | set(_PHONY))


@pytest.mark.parametrize("target", _TARGETS)
def test_makefile_runs_only_what_exists(target):
    assert target in _RULES, f".PHONY names {target!r}: no such rule"
    assert target in _PHONY, f"{target!r} is missing from .PHONY"
    prereqs, recipe = _RULES[target]
    for name in prereqs:
        assert name in _RULES, f"{target}: prerequisite {name!r} has no rule"
    for line in recipe:
        for path in re.findall(_PYTHON + r"([^\s-]\S*\.py)\b", line):
            if not _PATTERN_CHARS.search(path):
                assert os.path.isfile(os.path.join(REPO_ROOT, path)), (
                    f"{target}: runs {path}, which does not exist")
        for module in re.findall(_PYTHON + r"-m (\S+)", line):
            if not _PATTERN_CHARS.search(module):
                assert importlib.util.find_spec(module) is not None, (
                    f"{target}: runs -m {module}, which does not resolve")


# -- documents ----------------------------------------------------------------

DOCUMENTS = ("README.md", "PARITY.md", "BASELINE.md", "examples/README.md",
             "deep_vision_tpu/obs/README.md")
_CHECKED_DIRS = ("deep_vision_tpu", "tools", "tests", "benchmark", "examples",
                 "native")
# history: these name what was there when they were written
_EXEMPT = {"PERF.md", "ROADMAP.md", "CHANGES.md"}
_PATH = re.compile(r"^[\w.{},/-]+\.(?:py|jsonl?|md)$")


def _expand_braces(token):
    m = re.search(r"\{([^{}]*)\}", token)
    if m is None:
        return [token]
    return list(itertools.chain.from_iterable(
        _expand_braces(token[:m.start()] + alt + token[m.end():])
        for alt in m.group(1).split(",")))


def _named_paths(text):
    """Repo-relative source, record and document paths inside backticks:
    under a source directory, or a `.py` / `.md` file at the top level. A
    name with any other first directory (`artifacts/`, a checkpoint or work
    directory), like a bare `journal.jsonl`, is the output or the argument
    of a command and is not checked."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            # `path.py:123`, `path.py::test_name`, a sentence's comma
            word = re.sub(r"(?:::?[\w\[\]-]+)+$", "", word).rstrip(".,;:)")
            if _PATTERN_CHARS.search(word) or not _PATH.match(word):
                continue
            for path in _expand_braces(word.removeprefix("./")):
                head, _, rest = path.partition("/")
                bare_source = not rest and path.endswith((".py", ".md"))
                if (head in _CHECKED_DIRS or bare_source) \
                        and path not in _EXEMPT:
                    yield path


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_documents_name_only_files_that_exist(doc):
    # a document may name its neighbours relative to its own directory
    roots = (REPO_ROOT, os.path.join(REPO_ROOT, os.path.dirname(doc)))
    missing = sorted({p for p in _named_paths(_read(doc)) if not any(
        os.path.isfile(os.path.join(root, p)) for root in roots)})
    assert not missing, f"{doc} names files that do not exist: {missing}"
