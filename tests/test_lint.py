"""Doc-registry lints (ISSUE 19 satellite): the AST sweeps that keep
docs/OBSERVABILITY.md honest, as a tier-1 gate.

Three lints:

* metric-name lint — every metric name used anywhere in the tree
  (``.inc(`` / ``.set_gauge(`` / ``.observe(`` with a literal name)
  must appear backtick-quoted in the doc's metric registry table. A
  counter nobody documented is a counter nobody reads.
* route lint — every ``/debug/*`` route registered in
  ``server/opsd.py`` must appear backtick-quoted in the doc's routes
  table. An undocumented debug route is a debug route nobody curls.
* path lint — every file or directory a document names in back-ticks
  exists in the tree. A pointer to a deleted file is a claim nobody can
  check (PR 30 deleted a benchmark stack that 60 such pointers named).
"""

import ast
import functools
import os
import pathlib
import re

import pytest

pytestmark = pytest.mark.telemetry

PKG_ROOT = pathlib.Path(__file__).resolve().parent.parent \
    / "fluidframework_tpu"
DOC = PKG_ROOT.parent / "docs" / "OBSERVABILITY.md"


# ------------------------------------------------------- metric-name lint

def metric_names_in_tree():
    """AST sweep of every ``.inc(`` / ``.set_gauge(`` / ``.observe(``
    call whose first argument names a metric: string literals verbatim,
    f-strings as their literal prefix + ``*`` (the per-reason counter
    families), and both arms of a literal conditional. ``observe``
    calls with a non-string first arg are ``Histogram.observe(value)``
    — not a name site. Returns ``{name: "file:line"}``."""
    roots = [PKG_ROOT, PKG_ROOT.parent / "tools"]
    files = []
    for r in roots:
        files += sorted(r.rglob("*.py")) if r.is_dir() else [r]
    kinds = {"inc", "set_gauge", "observe"}
    names = {}

    def literal_names(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return [node.value]
        if isinstance(node, ast.JoinedStr) and node.values and \
                isinstance(node.values[0], ast.Constant):
            return [str(node.values[0].value) + "*"]
        if isinstance(node, ast.IfExp):
            return literal_names(node.body) + literal_names(node.orelse)
        return []

    for path in files:
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in kinds and node.args):
                continue
            for name in literal_names(node.args[0]):
                names.setdefault(name, f"{path.name}:{node.lineno}")
    return names


def test_metric_names_all_in_observability_doc():
    doc = DOC.read_text()
    names = metric_names_in_tree()
    assert names, "AST sweep found no metric call sites — lint is broken"
    assert len(names) > 20, f"sweep saw too few sites: {sorted(names)}"
    missing = [f"{n} ({where})" for n, where in sorted(names.items())
               if f"`{n}`" not in doc]
    assert not missing, (
        "metric names missing from docs/OBSERVABILITY.md's registry "
        f"table: {missing}")


# ------------------------------------------------------------- route lint

def debug_routes_in_opsd():
    """AST sweep of ``server/opsd.py`` for ``.route("<path>", ...)``
    registrations. Returns ``{path: line}`` for every literal route."""
    src = (PKG_ROOT / "server" / "opsd.py").read_text()
    routes = {}
    for node in ast.walk(ast.parse(src)):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "route" and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            routes.setdefault(node.args[0].value, node.lineno)
    return routes


def test_all_debug_routes_documented():
    doc = DOC.read_text()
    routes = debug_routes_in_opsd()
    assert routes, "route sweep found nothing — lint is broken"
    assert any(r.startswith("/debug/") for r in routes), \
        f"no /debug routes found: {sorted(routes)}"
    missing = [f"{r} (opsd.py:{line})"
               for r, line in sorted(routes.items())
               if r.startswith("/debug/") and f"`{r}`" not in doc]
    assert not missing, (
        "/debug routes missing from docs/OBSERVABILITY.md's routes "
        f"table: {missing}")


# ------------------------------------------------------------- path lint

REPO = PKG_ROOT.parent
PATH_DOCS = (["README.md", "PERF.md", "ROADMAP.md", "PARITY.md",
              "DISTRIBUTED.md", ".claude/skills/verify/SKILL.md"]
             + sorted(f"docs/{p.name}" for p in (REPO / "docs").glob("*.md")))
#: names a document may hold though the tree does not: what it records
#: as deleted (PR 30), and files the program writes at run time
NOT_IN_TREE = {
    "bench.py", "benches/", "tools/bench_report.py",
    "tools/perf_sentinel.py", "BENCHES.md", "ADVICE.md",
    ".manifest.json", "epoch.json", "fence.json", "out.json",
}
_TICKED = re.compile(r"`([^`\n]+)`")
_PATH_LIKE = re.compile(r"^[\w.\-/]+(\.py|\.md|\.json|/)$")


@functools.lru_cache(maxsize=None)
def _tree():
    """Every file and directory of the checkout as ``/``-rooted posix
    names, less ``.git`` and the directories ``.gitignore`` lists."""
    skip = {".git"} | {
        ln.strip().rstrip("/")
        for ln in (REPO / ".gitignore").read_text().splitlines()
        if ln.strip().endswith("/")}
    files, dirs = [], []
    for d, subdirs, names in os.walk(REPO):
        subdirs[:] = [x for x in subdirs if x not in skip]
        rel = pathlib.Path(d).relative_to(REPO).as_posix()
        rel = "/" if rel == "." else f"/{rel}/"
        dirs += [f"{rel}{x}/" for x in subdirs]
        files += [f"{rel}{x}" for x in names]
    return files, dirs


def _named_paths(text):
    """Back-ticked tokens that name a file or directory of this repo:
    they end in ``.py``, ``.md``, ``.json`` or ``/``, hold no wildcard
    or placeholder, and are not absolute (``file.py:line`` and
    ``file.py::test`` name ``file.py``)."""
    for m in _TICKED.finditer(text):
        for tok in m.group(1).split():
            tok = tok.split(":")[0].strip("(),;'\"")
            if _PATH_LIKE.match(tok) and not tok.startswith("/"):
                yield tok


@pytest.mark.parametrize("doc", PATH_DOCS)
def test_paths_a_document_names_exist(doc):
    """A name may be given from the root or from any directory below it
    (``server/serving.py`` for ``fluidframework_tpu/server/serving.py``).
    Of ``ROADMAP.md`` only "Open items" is held to it: its history names
    what earlier PRs deleted."""
    text = (REPO / doc).read_text()
    if doc == "ROADMAP.md":
        text = text[text.index("## Open items"):text.index("## Recent")]
    files, dirs = _tree()
    missing = sorted({
        n for n in _named_paths(text)
        if n not in NOT_IN_TREE and not any(
            p.endswith("/" + n.lstrip("./"))
            for p in (dirs if n.endswith("/") else files))})
    assert not missing, f"{doc} names what the tree does not hold: {missing}"


def test_the_path_lint_sees_a_stale_pointer():
    text = ("see `server/serving.py:1570`, `python nope/gone.py --x`, "
            "`perfbench/metrics/<name>.json`, `docs/*.md`, `/root/x.json`")
    assert list(_named_paths(text)) == ["server/serving.py",
                                        "nope/gone.py"]
