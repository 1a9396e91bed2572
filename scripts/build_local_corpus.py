#!/usr/bin/env python
"""Harvest a real natural-language corpus from the local machine (no egress).

The build environment has zero network egress, so FineWeb-style hub
streaming can't supply real text. This builds an honest offline corpus of
English prose from what the image ships:

- documentation files (``*.rst``, ``*.md``, long ``*.txt``) under the
  Python environment and ``/usr/share/doc`` (incl. gzipped changelogs);
- docstrings extracted (via ``ast``) from installed Python packages and
  the standard library.

Output is a shuffled JSONL of ``{"text": ...}`` documents — the same shape
FineWeb prep produces — ready for tools/prepare_dataset.py (split +
tokenizer + config). This is real human-written prose with natural token
statistics, not ``rng.integers`` noise; the provenance is stated in the
produced ``<out-stem>.manifest.json``.

Usage:
    python scripts/build_local_corpus.py --out /tmp/corpus.jsonl \
        [--min-doc-chars 400] [--max-mb 200]
"""

from __future__ import annotations

import argparse
import ast
import glob
import gzip
import io
import json
import os
import random
import re
import sys

DOC_ROOTS = [
    "/opt/venv",
    "/usr/share/doc",
    "/usr/lib/python3.12",
]

_WS = re.compile(r"[ \t]+")
_MANY_NL = re.compile(r"\n{3,}")


def _clean(text: str) -> str:
    text = text.replace("\r\n", "\n").replace("\x00", "")
    text = _WS.sub(" ", text)
    text = _MANY_NL.sub("\n\n", text)
    return text.strip()


def _is_prose(text: str, min_chars: int) -> bool:
    if len(text) < min_chars:
        return False
    # mostly printable ASCII/latin, with a healthy share of letters+spaces
    letters = sum(c.isalpha() or c.isspace() for c in text)
    if letters / len(text) < 0.75:
        return False
    # require real sentences, not symbol tables
    return text.count(". ") + text.count(".\n") >= 3


def iter_doc_files(min_chars: int):
    seen = set()
    patterns = []
    for root in DOC_ROOTS:
        patterns += [
            os.path.join(root, "**", "*.rst"),
            os.path.join(root, "**", "*.md"),
            os.path.join(root, "**", "*.txt"),
            os.path.join(root, "**", "*.gz"),
        ]
    for pat in patterns:
        for path in glob.iglob(pat, recursive=True):
            real = os.path.realpath(path)
            if real in seen or not os.path.isfile(real):
                continue
            seen.add(real)
            try:
                if path.endswith(".gz"):
                    with gzip.open(real, "rt", errors="ignore") as f:
                        raw = f.read(4 << 20)
                else:
                    if os.path.getsize(real) < min_chars:
                        continue
                    with io.open(real, "r", errors="ignore") as f:
                        raw = f.read(4 << 20)
            except (OSError, EOFError):
                continue
            text = _clean(raw)
            if _is_prose(text, min_chars):
                yield text


def iter_docstrings(min_chars: int):
    """Module/class/function docstrings from installed Python source."""
    for root in ("/opt/venv/lib", "/usr/lib/python3.12"):
        for path in glob.iglob(os.path.join(root, "**", "*.py"), recursive=True):
            try:
                with io.open(path, "r", errors="ignore") as f:
                    src = f.read(2 << 20)
                tree = ast.parse(src)
            except (OSError, SyntaxError, ValueError):
                continue
            parts = []
            for node in ast.walk(tree):
                if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    ds = ast.get_docstring(node)
                    if ds and len(ds) > 120:
                        parts.append(ds)
            if not parts:
                continue
            text = _clean("\n\n".join(parts))
            if _is_prose(text, min_chars):
                yield text


def iter_source_files(min_chars: int, exts=(".py",)):
    """Whole source files as documents (real human-written text: code +
    comments + docstrings). Skips vendored/minified/test-fixture noise by
    requiring a minimum size and a sane line length profile."""
    seen = set()
    for root in ("/opt/venv/lib", "/usr/lib/python3.12"):
        for ext in exts:
            for path in glob.iglob(os.path.join(root, "**", f"*{ext}"),
                                   recursive=True):
                real = os.path.realpath(path)
                if real in seen or not os.path.isfile(real):
                    continue
                seen.add(real)
                try:
                    if os.path.getsize(real) < min_chars:
                        continue
                    with io.open(real, "r", errors="ignore") as f:
                        raw = f.read(1 << 20)
                except OSError:
                    continue
                text = raw.replace("\r\n", "\n").replace("\x00", "").strip()
                if len(text) < min_chars:
                    continue
                lines = text.splitlines()
                # minified/generated files have few, enormous lines
                if not lines or sum(len(l) for l in lines) / len(lines) > 200:
                    continue
                yield text


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", required=True)
    p.add_argument("--min-doc-chars", type=int, default=400)
    p.add_argument("--max-mb", type=float, default=200.0)
    p.add_argument("--code-mb", type=float, default=0.0,
                   help="additionally include up to this many MB of whole "
                        "source files (.py) as documents — real text with "
                        "different token statistics than the doc prose")
    p.add_argument("--seed", type=int, default=0)
    a = p.parse_args(argv)

    docs = []
    total = 0
    cap = int(a.max_mb * 1e6)
    # With --code-mb, whole .py files already carry their docstrings —
    # running the docstring extractor too would ship every long docstring
    # twice, so the prose side is then doc-files only.
    prose_iters = ((iter_doc_files(a.min_doc_chars),) if a.code_mb > 0 else
                   (iter_doc_files(a.min_doc_chars),
                    iter_docstrings(a.min_doc_chars)))
    for it in prose_iters:
        for text in it:
            docs.append(text)
            total += len(text)
            if total >= cap:
                break
        if total >= cap:
            break

    code_chars = 0
    if a.code_mb > 0:
        code_cap = int(a.code_mb * 1e6)
        for text in iter_source_files(a.min_doc_chars):
            docs.append(text)
            code_chars += len(text)
            if code_chars >= code_cap:
                break
        total += code_chars

    random.Random(a.seed).shuffle(docs)
    os.makedirs(os.path.dirname(os.path.abspath(a.out)) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        for text in docs:
            f.write(json.dumps({"text": text}) + "\n")
    manifest = {
        "documents": len(docs),
        "chars": total,
        "mb": round(total / 1e6, 1),
        "code_mb": round(code_chars / 1e6, 1),
        "sources": "local documentation (*.rst/*.md/*.txt, /usr/share/doc "
                   "gzipped changelogs)"
                   + (" + whole .py source files (docstrings ride along "
                      "in-file)" if code_chars
                      else " + installed-package docstrings"),
        "note": "offline real-prose corpus; zero-egress environment",
    }
    with open(os.path.splitext(a.out)[0] + ".manifest.json", "w") as f:
        json.dump(manifest, f, indent=2)
    print(json.dumps(manifest))
    return 0


if __name__ == "__main__":
    sys.exit(main())
