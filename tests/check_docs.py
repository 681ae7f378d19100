#!/usr/bin/env python3
"""Docs consistency checks — run by the CI `docs` job and by pytest.

Five checks, all stdlib-only (no jax import, so the CI job needs
nothing but a Python interpreter):

1. **Intra-repo markdown links resolve.** Every relative
   ``[text](path)`` link in the repo's tracked ``*.md`` files must
   point at an existing file/directory (``#fragment`` suffixes are
   stripped; ``http(s)://`` / ``mailto:`` links are skipped).

2. **docs/kernels.md backend matrix ↔ ops.BACKENDS sync.** The matrix
   rows between the ``<!-- BACKENDS:BEGIN/END -->`` markers must list
   exactly the backends of ``repro.kernels.mttkrp.ops.BACKENDS`` plus
   the two dispatch-level names (``auto``, ``segsum``). ``BACKENDS`` is
   read from the source with ``ast`` so adding a backend without
   documenting it (or vice versa) fails CI.

3. **"lowers (Mosaic)" column ↔ BENCH_lowering.json sync.** The
   matrix's lowering column may only say "yes" for a backend whose
   every row in ``experiments/bench/BENCH_lowering.json`` (the artifact
   the ``interpret=False`` AOT sweep writes) has ``lowered_ok``; a
   backend the sweep saw fail must say "no". Dispatch-level rows
   (no kernel to lower) must carry an em-dash. So the docs claim
   exactly what the checked-in sweep demonstrated.

4. **docs/observability.md counter table ↔ obs NAMESPACES sync.** The
   rows between the ``<!-- COUNTERS:BEGIN/END -->`` markers must list
   exactly the names of ``repro.obs.counters.NAMESPACES`` (read with
   ``ast``, like BACKENDS). The registry rejects undocumented names at
   runtime; this closes the loop the other way — a namespace entry
   without a doc row fails CI.

5. **docs/observability.md scope table ↔ obs SCOPES sync.** The rows
   between the ``<!-- SCOPES:BEGIN/END -->`` markers must list exactly
   the names of ``repro.obs.scopes.SCOPES`` (read with ``ast``): the
   phase names the production sweep opens with ``jax.named_scope``.

Exit status 0 iff all checks pass; failures are printed one per line.
"""
from __future__ import annotations

import ast
import json
import os
import re
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS_PATH = os.path.join(REPO_ROOT, "src", "repro", "kernels", "mttkrp",
                        "ops.py")
KERNELS_DOC = os.path.join(REPO_ROOT, "docs", "kernels.md")
LOWERING_BENCH = os.path.join(REPO_ROOT, "experiments", "bench",
                              "BENCH_lowering.json")
LOWERING_COLUMN = "lowers (Mosaic)"
COUNTERS_PATH = os.path.join(REPO_ROOT, "src", "repro", "obs",
                             "counters.py")
OBS_DOC = os.path.join(REPO_ROOT, "docs", "observability.md")
SCOPES_PATH = os.path.join(REPO_ROOT, "src", "repro", "obs", "scopes.py")

# Names the matrix documents beyond ops.BACKENDS: the auto resolver and
# the distributed layer's plain-XLA path.
DISPATCH_LEVEL_NAMES = {"auto", "segsum"}

_SKIP_DIRS = {".git", ".pytest_cache", "__pycache__", ".claude",
              "node_modules", ".venv"}
_LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_ROW_NAME_RE = re.compile(r"^\|\s*`([a-z0-9_]+)`")
# Counter names are dotted (`oocore.dma.scheduled_bytes`).
_COUNTER_ROW_RE = re.compile(r"^\|\s*`([a-z0-9_.]+)`")


def iter_markdown_files():
    for dirpath, dirnames, filenames in os.walk(REPO_ROOT):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
        for name in sorted(filenames):
            if name.endswith(".md"):
                yield os.path.join(dirpath, name)


def check_links() -> tuple[list[str], int]:
    """Returns (errors, number_of_links_checked)."""
    errors, checked = [], 0
    for md in iter_markdown_files():
        with open(md, encoding="utf-8") as f:
            text = f.read()
        for target in _LINK_RE.findall(text):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            checked += 1
            path = target.split("#", 1)[0]
            resolved = os.path.normpath(
                os.path.join(os.path.dirname(md), path))
            if not os.path.exists(resolved):
                rel = os.path.relpath(md, REPO_ROOT)
                errors.append(f"{rel}: broken link -> {target}")
    return errors, checked


def ops_backends() -> tuple[str, ...]:
    """`BACKENDS` from ops.py via ast — no jax import needed."""
    with open(OPS_PATH, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=OPS_PATH)
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "BACKENDS"
                for t in node.targets):
            value = ast.literal_eval(node.value)
            return tuple(value)
    raise AssertionError(f"no literal BACKENDS assignment found in "
                         f"{OPS_PATH}")


def documented_backends() -> set[str]:
    """Backend names in kernels.md's marked matrix rows."""
    with open(KERNELS_DOC, encoding="utf-8") as f:
        text = f.read()
    try:
        block = text.split("<!-- BACKENDS:BEGIN -->", 1)[1] \
                    .split("<!-- BACKENDS:END -->", 1)[0]
    except IndexError:
        raise AssertionError(
            "docs/kernels.md is missing the <!-- BACKENDS:BEGIN/END --> "
            "markers around the backend matrix")
    names = set()
    for line in block.splitlines():
        m = _ROW_NAME_RE.match(line.strip())
        if m:
            names.add(m.group(1))
    return names


def matrix_cells() -> tuple[list[str], dict[str, list[str]]]:
    """(header cells, {backend: row cells}) of the marked matrix."""
    with open(KERNELS_DOC, encoding="utf-8") as f:
        text = f.read()
    block = text.split("<!-- BACKENDS:BEGIN -->", 1)[1] \
                .split("<!-- BACKENDS:END -->", 1)[0]
    header, rows = [], {}
    for line in block.splitlines():
        line = line.strip()
        if not line.startswith("|") or set(line) <= {"|", "-", " "}:
            continue
        cells = [c.strip() for c in line.strip("|").split("|")]
        m = _ROW_NAME_RE.match(line)
        if m:
            rows[m.group(1)] = cells
        elif not header:
            header = cells
    return header, rows


def lowering_status() -> dict[str, bool]:
    """{backend: every sweep point lowered_ok} from BENCH_lowering.json."""
    with open(LOWERING_BENCH, encoding="utf-8") as f:
        data = json.load(f)
    status: dict[str, bool] = {}
    for row in data:
        if row.get("bench") != "lowering":
            continue
        b = row["backend"]
        status[b] = status.get(b, True) and bool(row["lowered_ok"])
    return status


def check_lowering_sync() -> list[str]:
    """The matrix's "lowers (Mosaic)" column matches the sweep artifact."""
    if not os.path.exists(LOWERING_BENCH):
        return [f"{os.path.relpath(LOWERING_BENCH, REPO_ROOT)} is missing "
                "— run `PYTHONPATH=src python -m benchmarks.run --only "
                "lowering` and commit the artifact"]
    errors = []
    header, rows = matrix_cells()
    if LOWERING_COLUMN not in header:
        return [f"docs/kernels.md: matrix has no `{LOWERING_COLUMN}` "
                "column"]
    col = header.index(LOWERING_COLUMN)
    status = lowering_status()
    if not status:
        return [f"{os.path.relpath(LOWERING_BENCH, REPO_ROOT)} has no "
                "lowering rows"]
    for name, cells in sorted(rows.items()):
        if len(cells) <= col:
            errors.append(f"docs/kernels.md: row `{name}` is short a "
                          f"`{LOWERING_COLUMN}` cell")
            continue
        cell = cells[col]
        if name in DISPATCH_LEVEL_NAMES:
            if cell not in {"—", "-", "n/a"}:
                errors.append(
                    f"docs/kernels.md: dispatch-level `{name}` has no "
                    f"kernel to lower; `{LOWERING_COLUMN}` must be an "
                    f"em-dash, not {cell!r}")
            continue
        if name not in status:
            errors.append(
                f"docs/kernels.md: backend `{name}` has no rows in "
                f"BENCH_lowering.json — extend the sweep before "
                "claiming a lowering status")
            continue
        want = "yes" if status[name] else "no"
        if not cell.startswith(want):
            errors.append(
                f"docs/kernels.md: `{name}` `{LOWERING_COLUMN}` says "
                f"{cell!r} but BENCH_lowering.json records "
                f"lowered_ok={status[name]} — the docs may only claim "
                "what the sweep demonstrated")
    return errors


def literal_tuple(path: str, name: str) -> tuple[str, ...]:
    """A module-level pure-literal tuple ``name`` from ``path``, via ast."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets):
            return tuple(ast.literal_eval(node.value))
    raise AssertionError(f"no literal {name} assignment found in {path}")


def documented_rows(doc: str, marker: str) -> set[str]:
    """First-column names of the table between ``<!-- marker:BEGIN/END
    -->`` in ``doc``."""
    with open(doc, encoding="utf-8") as f:
        text = f.read()
    try:
        block = text.split(f"<!-- {marker}:BEGIN -->", 1)[1] \
                    .split(f"<!-- {marker}:END -->", 1)[0]
    except IndexError:
        raise AssertionError(
            f"{os.path.relpath(doc, REPO_ROOT)} is missing the "
            f"<!-- {marker}:BEGIN/END --> markers")
    names = set()
    for line in block.splitlines():
        m = _COUNTER_ROW_RE.match(line.strip())
        if m:
            names.add(m.group(1))
    return names


def obs_namespaces() -> tuple[str, ...]:
    """`NAMESPACES` from obs/counters.py via ast — no jax import."""
    return literal_tuple(COUNTERS_PATH, "NAMESPACES")


def documented_counters() -> set[str]:
    """Counter names in observability.md's marked table rows."""
    return documented_rows(OBS_DOC, "COUNTERS")


def check_counter_sync() -> list[str]:
    errors = []
    code = set(obs_namespaces())
    docs = documented_counters()
    for missing in sorted(code - docs):
        errors.append(
            f"docs/observability.md: counter `{missing}` is in "
            "obs.counters.NAMESPACES but missing from the namespace "
            "table")
    for stale in sorted(docs - code):
        errors.append(
            f"docs/observability.md: counter `{stale}` is documented "
            "but not in obs.counters.NAMESPACES — remove the row or "
            "register the name")
    return errors


def check_scope_sync() -> list[str]:
    code = set(literal_tuple(SCOPES_PATH, "SCOPES"))
    docs = documented_rows(OBS_DOC, "SCOPES")
    return ([f"docs/observability.md: scope `{s}` is in obs.scopes.SCOPES "
             "but missing from the scope table" for s in sorted(code - docs)]
            + [f"docs/observability.md: scope `{s}` is documented but not "
               "in obs.scopes.SCOPES" for s in sorted(docs - code)])


def check_backend_sync() -> list[str]:
    errors = []
    code = set(ops_backends())
    docs = documented_backends()
    want = code | DISPATCH_LEVEL_NAMES
    for missing in sorted(want - docs):
        errors.append(
            f"docs/kernels.md: backend `{missing}` exists in ops.py "
            "(or is a dispatch-level name) but is missing from the "
            "decision matrix")
    for stale in sorted(docs - want):
        errors.append(
            f"docs/kernels.md: backend `{stale}` is documented but not "
            "in ops.BACKENDS — remove the row or add the backend")
    return errors


def main() -> int:
    link_errors, checked = check_links()
    sync_errors = check_backend_sync()
    lowering_errors = check_lowering_sync()
    counter_errors = check_counter_sync()
    scope_errors = check_scope_sync()
    errors = (link_errors + sync_errors + lowering_errors + counter_errors
              + scope_errors)
    for e in errors:
        print(f"FAIL {e}")
    if errors:
        return 1
    n_backends = len(ops_backends())
    n_lower = sum(lowering_status().values())
    print(f"docs checks passed: {checked} markdown links resolve, "
          f"{n_backends} backends in sync with docs/kernels.md, "
          f"{n_lower} lowering statuses match BENCH_lowering.json, "
          f"{len(obs_namespaces())} counters and "
          f"{len(literal_tuple(SCOPES_PATH, 'SCOPES'))} scopes in sync "
          "with docs/observability.md")
    return 0


if __name__ == "__main__":
    sys.exit(main())
