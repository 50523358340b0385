#!/usr/bin/env python
"""Docs coverage / link check (``make docs-check``).

Verifies that the documentation keeps up with the code:

  1. every package directory under ``src/repro/`` is mentioned by name
     somewhere in README.md or docs/*.md;
  2. every relative link and bare file reference in README.md and
     docs/*.md resolves to a real file in the repo;
  3. every ``benchmarks/bench_*.py`` entry point is documented in
     docs/benchmarks.md;
  4. every backticked dotted module reference (``repro.fleet.perf``,
     optionally with a trailing attribute or ``::anchor``) resolves to a
     module under ``src/``;
  5. every ``--flag`` on a ``python ...`` command line inside a fenced
     code block appears verbatim in the source of the script/module the
     command invokes (so documented CLI surfaces can't drift);
  6. every backticked ``serve_*`` / ``train_*`` / ``attn_*`` metric name in
     docs/observability.md exists in ``src/repro/obs/`` (the catalog
     table can't drift from the pinned metric vocabulary);
  7. every ``benchmarks/scenarios/*.json`` validates against the
     scenario schema (``repro.fleet.scenarios::validate_scenario`` —
     unknown keys and non-reproducible seeds are rejected) and its
     ``name`` is documented somewhere in the docs corpus.

Exits non-zero with a report on failure. Wired into scripts/tier1.sh as
a *fatal* gate: docs drift blocks the tier-1 verify.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def doc_files():
    files = [ROOT / "README.md"]
    files += sorted((ROOT / "docs").glob("*.md"))
    return [f for f in files if f.exists()]


def module_ref_resolves(ref: str) -> bool:
    """``repro.a.b`` -> src/repro/a/b.py or the package src/repro/a/b/.

    A trailing component may be a function/class attribute — but only of
    a *module* (``repro.core.goodput.modeled_goodput`` is fine because
    goodput.py exists); a dangling name under a package directory
    (``repro.fleet.nonexistent``) does not resolve."""
    parts = ref.split("::", 1)[0].split(".")
    base = ROOT / "src" / Path(*parts)
    if base.with_suffix(".py").exists() or base.is_dir():
        return True
    prefix = ROOT / "src" / Path(*parts[:-1]) if len(parts) > 1 else None
    return prefix is not None and prefix.with_suffix(".py").exists()


def fenced_blocks(text: str):
    """Yield the contents of ``` fenced code blocks."""
    chunks = text.split("```")
    for i in range(1, len(chunks), 2):
        body = chunks[i]
        # drop the info string (first line, e.g. "sh" or "python")
        yield body.split("\n", 1)[1] if "\n" in body else ""


def command_target(tokens) -> Path | None:
    """The repo file a ``python ...`` command line invokes, if any."""
    for j, tok in enumerate(tokens):
        if tok == "-m" and j + 1 < len(tokens):
            mod = tokens[j + 1]
            for base in (ROOT, ROOT / "src"):
                p = base / (mod.replace(".", "/") + ".py")
                if p.exists():
                    return p
            return None
        if tok.endswith(".py"):
            p = ROOT / tok
            return p if p.exists() else None
    return None


def check_cli_flags(doc: Path, problems) -> None:
    for block in fenced_blocks(doc.read_text()):
        # join continuation lines so flags after a trailing \ attach to
        # their command
        joined = re.sub(r"\\\n\s*", " ", block)
        for line in joined.splitlines():
            if "python" not in line:
                continue
            tokens = [t.strip("[]()") for t in line.split()]
            target = command_target(tokens)
            if target is None:
                continue
            src = target.read_text()
            for tok in tokens:
                m = re.match(r"(--[A-Za-z][\w-]*)", tok)
                if m and m.group(1) not in src:
                    problems.append(
                        f"{doc.relative_to(ROOT)}: flag {m.group(1)} not "
                        f"found in {target.relative_to(ROOT)}")


def main() -> int:
    problems = []
    docs = doc_files()
    if not (ROOT / "README.md").exists():
        problems.append("README.md is missing")
    if not (ROOT / "docs").is_dir():
        problems.append("docs/ directory is missing")
    corpus = "\n".join(f.read_text() for f in docs)

    # 1) every src/repro/* package is mentioned somewhere in the docs
    for pkg in sorted((ROOT / "src" / "repro").iterdir()):
        if not pkg.is_dir() or pkg.name.startswith("__"):
            continue
        if pkg.name not in corpus:
            problems.append(
                f"package src/repro/{pkg.name}/ is not mentioned in "
                f"README.md or docs/")

    # 2) markdown links + bare path references resolve
    path_re = re.compile(
        r"\]\(([^)]+?)\)"                     # [text](target[#anchor])
        r"|`((?:src|docs|benchmarks|scripts|tests|examples)"
        r"/[\w./-]+?)(?:::[\w.]+)?`")         # `path/to/file.py::anchor`
    for f in docs:
        for m in path_re.finditer(f.read_text()):
            target = (m.group(1) or m.group(2)).split("#", 1)[0]
            if not target or target.startswith(
                    ("http://", "https://", "mailto:")):
                continue
            resolved = (f.parent / target).resolve()
            alt = (ROOT / target).resolve()
            if not resolved.exists() and not alt.exists():
                problems.append(f"{f.relative_to(ROOT)}: broken link "
                                f"-> {target}")

    # 3) every benchmark entry point is documented
    bench_doc = ROOT / "docs" / "benchmarks.md"
    bench_text = bench_doc.read_text() if bench_doc.exists() else ""
    for bench in sorted((ROOT / "benchmarks").glob("bench_*.py")):
        if bench.name not in bench_text:
            problems.append(
                f"benchmarks/{bench.name} is not documented in "
                f"docs/benchmarks.md")

    # 4) backticked dotted module references resolve under src/
    mod_re = re.compile(r"`(repro(?:\.\w+)+(?:::[\w.]+)?)`")
    for f in docs:
        for m in mod_re.finditer(f.read_text()):
            if not module_ref_resolves(m.group(1)):
                problems.append(f"{f.relative_to(ROOT)}: module ref "
                                f"`{m.group(1)}` does not resolve "
                                f"under src/")

    # 5) documented CLI flags exist in the script they are shown with
    for f in docs:
        check_cli_flags(f, problems)

    # 6) metric names in the observability catalog exist in the obs
    # package (repro.obs.metrics.CATALOG is the pinned vocabulary)
    obs_doc = ROOT / "docs" / "observability.md"
    if obs_doc.exists():
        obs_src = "\n".join(p.read_text() for p in sorted(
            (ROOT / "src" / "repro" / "obs").glob("*.py")))
        for m in re.finditer(r"`((?:serve|train|attn)_[a-z0-9_]+)`",
                             obs_doc.read_text()):
            if m.group(1) not in obs_src:
                problems.append(
                    f"docs/observability.md: metric `{m.group(1)}` not "
                    f"found in src/repro/obs/")

    # 7) scenario suites validate and are documented
    sys.path.insert(0, str(ROOT / "src"))
    from repro.fleet.scenarios import load_scenario_paths, \
        validate_scenario
    import json
    scen_paths = load_scenario_paths(ROOT / "benchmarks" / "scenarios")
    if not scen_paths:
        problems.append("benchmarks/scenarios/ has no scenario files")
    for p in scen_paths:
        doc = json.loads(p.read_text())
        for issue in validate_scenario(doc):
            problems.append(
                f"{p.relative_to(ROOT)}: invalid scenario — {issue}")
        name = doc.get("name", "")
        if name and name not in corpus:
            problems.append(
                f"scenario `{name}` ({p.relative_to(ROOT)}) is not "
                f"documented in README.md or docs/")

    if problems:
        print("docs-check FAILED:")
        for p in problems:
            print(f"  - {p}")
        return 1
    print(f"docs-check OK: {len(docs)} docs, all packages mentioned, "
          f"all links, module refs and CLI flags resolve, "
          f"{len(scen_paths)} scenario suites validate")
    return 0


if __name__ == "__main__":
    sys.exit(main())
