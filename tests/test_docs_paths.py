"""The documents an owner reads first name only files and ``make`` targets
that exist. One case a document; ``ROADMAP.md``, ``CHANGES.md``, ``PERF.md``
and ``SURVEY.md`` are history and are not checked."""
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "docs/PERFORMANCE.md", "docs/OBSERVABILITY.md",
             "docs/ANALYSIS.md", "docs/ROBUSTNESS.md", "docs/SERVING.md",
             ".claude/skills/verify/SKILL.md", "Makefile"]
TOP = ("tools", "tests", "mxnet_tpu", "benchmark", "docs", "example", "native")
# where a path written without its top directory is looked for
HOMES = ("", "mxnet_tpu", "benchmark", "tools")

ROOTED = re.compile(r"(?<![\w/.<>-])((?:%s)/[\w./-]*\w/?)" % "|".join(TOP))
# a script named in backticks or run in a recipe: `x.py`, `serve/x.py --flag`
SCRIPT = re.compile(r"(?:`|\bpython3? )((?:\w+/)*\w+\.py)\b")
MAKE = re.compile(r"(?:`|^\s*(?:\$ )?)make ([a-z][\w-]*)", re.M)


def _scripts():
    """Every ``*.py`` by its bare name: at the root and under ``TOP``."""
    found = {f for f in os.listdir(REPO) if f.endswith(".py")}
    for top in TOP:
        for _folder, _dirs, files in os.walk(os.path.join(REPO, top)):
            found.update(f for f in files if f.endswith(".py"))
    return found


def _targets():
    with open(os.path.join(REPO, "Makefile")) as f:
        return set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))


def _exists(path):
    return any(os.path.exists(os.path.join(REPO, home, path))
               for home in HOMES)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    scripts = _scripts()
    missing = sorted(
        {p for p in ROOTED.findall(text)
         if not os.path.exists(os.path.join(REPO, p))}
        | {p for p in SCRIPT.findall(text)
           if not (_exists(p) if "/" in p else p in scripts)})
    assert not missing, f"{document} names files that are not there: {missing}"
    gone = sorted(set(MAKE.findall(text)) - _targets())
    assert not gone, f"{document} names make targets that are not there: {gone}"
