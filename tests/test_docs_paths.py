"""Every repository path a document names exists.

One case a document (README.md, docs/*.md, docs/faq/*.md,
examples/README.md). A path is a token inside backticks or inside a
fenced block that either starts with a directory of the checkout
(``tools/x.py``, ``tests/x.py``, ``mxnet_tpu/...``, ``examples/...``; a
directory of ``mxnet_tpu/`` or of the document's own directory counts
where the token has a suffix: ``serve2/decode.py``), or is a bare
script or document name (``chip_smoke.py``; in a fenced block only the
script ``python`` runs, since its arguments are the reader's own
files). ``dir/module.name``
stands for ``dir/module.py``. Paths of the reference project
(``src/operator/...``) start with no directory of this checkout and
are not looked at. A document that sends its reader to a script that is
gone fails here (ROADMAP.md D14).
"""
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = sorted(
    os.path.relpath(p, ROOT) for p in
    [os.path.join(ROOT, "README.md"),
     os.path.join(ROOT, "examples", "README.md")]
    + glob.glob(os.path.join(ROOT, "docs", "*.md"))
    + glob.glob(os.path.join(ROOT, "docs", "faq", "*.md")))

DIRS = ("tools", "tests", "mxnet_tpu", "examples", "docs", "benchmark",
        "perl-package")
SKIP_DIRS = {".git", "__pycache__", "chiprun_out", ".jax_cache",
             ".chip_smoke_state", ".bench_tmp"}
# what a run leaves behind or a reader supplies, not a file of the checkout
PLACEHOLDER = re.compile(r"[<>{}*$%]|\.\.\.|^/|^~|://")
TOKEN = re.compile(r"[A-Za-z0-9_.\-]+(?:/[A-Za-z0-9_.\-]+)*/?")


@pytest.fixture(scope="module")
def basenames():
    """The name of every file of the checkout: one walk for all cases."""
    names = set()
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        names.update(files)
    return names


def _spans(text):
    """(words, fenced) for each fenced line and each backticked span."""
    fenced = False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif fenced:
            yield line.split(), True
        else:
            for span in re.findall(r"`([^`]+)`", line):
                yield span.split(), False


def _named(text, doc_dir):
    """(token, bases it may resolve from) for every path the text names."""
    for words, fenced in _spans(text):
        for prev, word in zip([""] + words, words):
            word = word.strip("()[],;'\"").split(":")[0]
            if PLACEHOLDER.search(word) or not TOKEN.fullmatch(word):
                continue
            tok = word.rstrip(".")
            head, slash, _ = tok.partition("/")
            if not slash:
                if tok.endswith((".py", ".md")) and (
                        not fenced or prev in ("python", "python3")):
                    yield tok, (ROOT, doc_dir)
            elif head in DIRS:
                yield tok, (ROOT,)
            elif os.path.splitext(tok)[1] and not tok.startswith("."):
                bases = tuple(b for b in (os.path.join(ROOT, "mxnet_tpu"),
                                          doc_dir)
                              if os.path.isdir(os.path.join(b, head)))
                if bases:
                    yield tok, bases


def _exists(tok, bases, basenames):
    stem = tok.rsplit(".", 1)[0] + ".py"  # tools/x.main -> tools/x.py
    if any(os.path.exists(os.path.join(b, t))
           for b in bases for t in (tok, stem)):
        return True
    return "/" not in tok and tok in basenames


def test_the_documents_are_found():
    assert "README.md" in DOCS and len(DOCS) > 10


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc, basenames):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    doc_dir = os.path.dirname(os.path.join(ROOT, doc))
    missing = sorted({tok for tok, bases in _named(text, doc_dir)
                      if not _exists(tok, bases, basenames)})
    assert not missing, f"{doc} names paths that do not exist: {missing}"
