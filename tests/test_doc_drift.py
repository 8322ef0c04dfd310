"""docs must not drift from the registries and the tree they point at.

docs/OBSERVABILITY.md's pinned metric-names table is machine-checked
against the live ``observe.metrics.CATALOG`` (names, types, AND label
keys), so adding or renaming a metric without updating the doc of
record is red; docs/SERVING.md's error-code table is held to
``robust.errors.SERVING_ERROR_CODES`` the same way.

And every path a document names exists (``TestDocsPointAtTheTree``): a
pointer to a script, a test or a record that has been deleted is the
drift that cost most here.
"""

import ast
import functools
import io
import re
import tokenize
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


OBS_DOC = REPO / "docs" / "OBSERVABILITY.md"

_METRICS_TABLE_RE = re.compile(
    r"<!--\s*METRICS_TABLE:BEGIN\s*-->(.*?)<!--\s*METRICS_TABLE:END\s*-->",
    re.S)


def _pinned_metrics():
    m = _METRICS_TABLE_RE.search(OBS_DOC.read_text())
    assert m, "OBSERVABILITY.md lost its METRICS_TABLE markers"
    pinned = {}
    for line in m.group(1).splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) != 3 or cells[0] in ("metric", "") or "---" in cells[0]:
            continue
        labels = tuple(sorted(x.strip() for x in cells[2].split(",")
                              if x.strip()))
        pinned[cells[0]] = (cells[1], labels)
    assert pinned, "pinned metrics table is empty"
    return pinned


class TestObservabilityDocDrift:
    """docs/OBSERVABILITY.md's metric table == observe.metrics.CATALOG."""

    def test_pinned_metric_names_match_catalog(self):
        from analytics_zoo_tpu.observe.metrics import CATALOG
        pinned = _pinned_metrics()
        missing = sorted(set(CATALOG) - set(pinned))
        stale = sorted(set(pinned) - set(CATALOG))
        assert not missing, \
            f"CATALOG metrics missing from OBSERVABILITY.md: {missing}"
        assert not stale, \
            f"OBSERVABILITY.md pins metrics not in CATALOG: {stale}"

    def test_pinned_types_and_labels_match_catalog(self):
        from analytics_zoo_tpu.observe.metrics import CATALOG
        bad = []
        for name, (typ, labels) in _pinned_metrics().items():
            if name not in CATALOG:
                continue
            cat_typ, _, cat_labels = CATALOG[name]
            if typ != cat_typ:
                bad.append(f"{name}: doc type={typ} catalog={cat_typ}")
            if labels != tuple(sorted(cat_labels)):
                bad.append(f"{name}: doc labels={labels} "
                           f"catalog={tuple(sorted(cat_labels))}")
        assert not bad, ("OBSERVABILITY.md drifted from CATALOG:\n  "
                         + "\n  ".join(bad))


SERVING_DOC = REPO / "docs" / "SERVING.md"

_ERROR_CODE_TABLE_RE = re.compile(
    r"<!--\s*ERROR_CODE_TABLE:BEGIN\s*-->(.*?)<!--\s*ERROR_CODE_TABLE:END\s*-->",
    re.S)


def _pinned_error_codes():
    m = _ERROR_CODE_TABLE_RE.search(SERVING_DOC.read_text())
    assert m, "SERVING.md lost its ERROR_CODE_TABLE markers"
    codes = {}
    for line in m.group(1).splitlines():
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) != 3 or cells[0] in ("code", "") or "---" in cells[0]:
            continue
        codes[cells[0].strip("`")] = cells[2]
    assert codes, "pinned error-code table is empty"
    return codes


class TestServingErrorCodeDocDrift:
    """docs/SERVING.md "Failure semantics" code table ==
    robust.errors.SERVING_ERROR_CODES: every stable code a typed
    serving error payload may carry is pinned in the doc of record,
    and the doc pins nothing the registry doesn't declare."""

    def test_pinned_codes_match_registry(self):
        from analytics_zoo_tpu.robust.errors import SERVING_ERROR_CODES
        pinned = _pinned_error_codes()
        missing = sorted(set(SERVING_ERROR_CODES) - set(pinned))
        stale = sorted(set(pinned) - set(SERVING_ERROR_CODES))
        assert not missing, \
            f"registry codes missing from SERVING.md: {missing}"
        assert not stale, \
            f"SERVING.md pins codes not in SERVING_ERROR_CODES: {stale}"

    def test_every_registry_code_is_a_declared_class_attr(self):
        """The registry is live, not aspirational: each code is the
        ``code`` of a typed exception (or the base class default)."""
        from analytics_zoo_tpu.robust import errors as E
        declared = {getattr(cls, "code")
                    for cls in vars(E).values()
                    if isinstance(cls, type) and hasattr(cls, "code")}
        # decode_error / model_error are emitted via
        # ServingError(code=...) at their stages, not dedicated classes
        assert (set(E.SERVING_ERROR_CODES) - declared
                == {"decode_error", "model_error"})


PACKAGE = REPO / "analytics_zoo_tpu"

# the documents that describe the tree as it is (PERF.md, ROADMAP.md and
# CHANGES.md are histories and name what is gone)
DOCUMENTS = ["README.md",
             *sorted(f"docs/{p.name}" for p in (REPO / "docs").glob("*.md")),
             ".claude/skills/verify/SKILL.md"]

_TREE = ("analytics_zoo_tpu/", "benchmark/", "tests/", "docs/", "examples/")
_PACKAGES = tuple(f"{p.name}/" for p in PACKAGE.iterdir() if p.is_dir())
_ROOT_SCRIPT = re.compile(r"^\w+\.py$")
_ROOT_RECORD = re.compile(r"^[A-Z][A-Z0-9]*(_\w+)?\.(json|jsonl|md)$")
_LINE_OR_TEST = re.compile(r"(::[\w\[\]\-.:]+|:\d[\d,\-–]*)$")
_PATTERN_CHARS = set("<>*{}[]…$")
_FILE_SUFFIXES = (".py", ".md", ".json", ".jsonl")
_WRAPPING = "`()\"'.,;:"


@functools.lru_cache(maxsize=None)
def _package_sources():
    return "\n".join(p.read_text() for p in sorted(PACKAGE.rglob("*.py")))


def dangling_paths(text, source=False):
    """The paths into the tree that ``text`` names and the tree lacks.

    The rule.  A word names a path into the tree when it

    - starts with ``analytics_zoo_tpu/``, ``benchmark/``, ``tests/``,
      ``docs/`` or ``examples/``; or
    - starts with a package of ``analytics_zoo_tpu/`` and ends in ``.py``
      or ``/`` (the documents' shorthand, ``ops/flash_attention.py``),
      and is then looked for under ``analytics_zoo_tpu/``; or
    - has no directory and is a ``*.py`` (a root-level script); or
    - has no directory and is an upper-case ``.json``, ``.jsonl`` or
      ``.md`` (a root-level record such as ``BENCH_r05.json``),

    with a trailing ``:line`` or ``::test`` stripped first.  Outside the
    rule: a pattern (anything with ``<``, ``>``, ``*``, braces, brackets
    or ``$``: ``<cell>``, ``tests/test_*.py``), and what a run writes
    (``flight_NNNN.json``, a model's ``config.json``: lower-case bare
    names are not records; a checkpoint's ``MANIFEST.json``: a bare name
    that the package holds as a string literal is a file the program
    writes, not one the repo keeps).

    In a document only back-ticked words count.  In the comments and
    docstrings of a source file (``source``) nothing is back-ticked by
    habit, so every word counts, and prose with a slash in it has to be
    told from a path: there a word of the first kind counts only when it
    ends in ``.py``, ``.md``, ``.json`` or ``.jsonl``, and the second and
    third kinds are left out (a bare ``model.py`` in a docstring is the
    module beside it or the reference project's).
    """
    spans = [text] if source else re.findall(r"`([^`\n]+)`", text)
    missing = set()
    for span in spans:
        for word in span.split():
            word = _LINE_OR_TEST.sub("", word.strip(_WRAPPING))
            word = word.rstrip(_WRAPPING)
            if not word or _PATTERN_CHARS & set(word):
                continue
            bare = "/" not in word
            if word.startswith(_TREE):
                if source and not word.endswith(_FILE_SUFFIXES):
                    continue
                found = (REPO / word).exists()
            elif bare and _ROOT_RECORD.match(word):
                found = ((REPO / word).exists()
                         or f'"{word}"' in _package_sources())
            elif source:
                continue
            elif bare and _ROOT_SCRIPT.match(word):
                found = (REPO / word).exists()
            elif word.startswith(_PACKAGES) and word.endswith((".py", "/")):
                found = (PACKAGE / word).exists()
            else:
                continue
            if not found:
                missing.add(word)
    return sorted(missing)


def _comments_and_docstrings(source):
    """What a source file says to its reader: comments and docstrings,
    no code and no other string."""
    out = [tok.string for tok in
           tokenize.generate_tokens(io.StringIO(source).readline)
           if tok.type == tokenize.COMMENT]
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            out.append(ast.get_docstring(node, clean=False) or "")
    return "\n".join(out)


class TestDocsPointAtTheTree:
    """One instrument (``benchmark/run.py``), one record
    (``PERF_LEDGER.jsonl``): no document sends a reader to a file the
    tree no longer has."""

    @pytest.mark.parametrize("document", DOCUMENTS)
    def test_every_path_a_document_names_exists(self, document):
        missing = dangling_paths((REPO / document).read_text())
        assert not missing, f"{document} names what the tree lacks: {missing}"

    def test_the_rule_catches_a_dangling_pointer(self):
        text = ("Run `python bench.py` and compare with `BENCH_r05.json`; "
                "the kernel is `analytics_zoo_tpu/ops/nope.py:12` "
                "(`ops/nope_too.py`), tested by "
                "`tests/test_doc_drift.py::TestDocsPointAtTheTree`, and "
                "writes `flight_0001.json` and `MANIFEST.json`; "
                "bench.py without back-ticks is prose.")
        assert dangling_paths(text) == [
            "BENCH_r05.json", "analytics_zoo_tpu/ops/nope.py", "bench.py",
            "ops/nope_too.py"]
        comment = "# pinned in SLO_r18.json (docs/GONE.md); see tests/callers"
        assert dangling_paths(comment, source=True) == [
            "SLO_r18.json", "docs/GONE.md"]

    def test_no_source_file_names_a_missing_record(self):
        missing = {}
        for root in (PACKAGE, REPO / "examples"):
            for path in sorted(root.rglob("*.py")):
                said = _comments_and_docstrings(path.read_text())
                gone = dangling_paths(said, source=True)
                if gone:
                    missing[str(path.relative_to(REPO))] = gone
        assert not missing, f"source files name what the tree lacks: {missing}"
