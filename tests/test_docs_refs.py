"""The documents name what exists: every ``TORCHSNAPSHOT_TPU_*`` variable
a document cites is a registered knob, and every backticked ``*.py`` path
it cites is a file of this repository.  A PR that deletes a knob or a
file without its document fails here."""

import glob
import os
import re

import pytest

from torchsnapshot_tpu import knobs

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCS = ["README.md"] + sorted(
    os.path.relpath(p, _ROOT)
    for p in glob.glob(os.path.join(_ROOT, "docs", "*.md"))
)
# a cited path is relative to the repo root or to the package (the docs'
# habit: `preparers/array.py`)
_BASES = ("", "torchsnapshot_tpu")
_ENV_NAME = re.compile(r"TORCHSNAPSHOT_TPU_([A-Z][A-Z0-9_]*[A-Z0-9])")
_BACKTICKED = re.compile(r"`([^`\n]+)`")
_PY_PATH = re.compile(r"^[\w./-]+\.py$")


def _cited_paths(text):
    for quoted in _BACKTICKED.findall(text):
        for token in quoted.split():
            token = token.split(":")[0].rstrip(",;)").lstrip("(")
            if _PY_PATH.match(token):
                yield token


@pytest.mark.parametrize("doc", _DOCS)
def test_a_document_cites_only_what_exists(doc):
    with open(os.path.join(_ROOT, doc)) as f:
        text = f.read()
    unknown_knobs = sorted(
        {n for n in _ENV_NAME.findall(text) if n not in knobs._DEFAULTS}
    )
    assert unknown_knobs == [], f"{doc} cites knobs that are not registered"
    missing = sorted(
        {
            p
            for p in _cited_paths(text)
            if not any(
                os.path.isfile(os.path.join(_ROOT, base, p)) for base in _BASES
            )
        }
    )
    assert missing == [], f"{doc} cites files that do not exist"
