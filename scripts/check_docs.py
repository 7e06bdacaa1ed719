#!/usr/bin/env python
"""Docs consistency gate (CI `docs` job).

Five checks, all cheap and dependency-free:

1. **README file references** — every path-looking token in README.md
   (backticked or inside fenced code blocks, containing a `/` or a known
   source suffix) must exist in the repo. Catches entry points that moved
   or were renamed after the docs were written.
2. **README CLI flags** — every `--flag` README mentions must be defined
   somewhere under `src/repro/launch/`, `benchmarks/` or `experiments/`
   (argparse
   definitions are greppable as string literals). Catches documented
   flags that were dropped or renamed.
3. **DESIGN.md section cross-references** — every explicit DESIGN.md
   section reference anywhere in the repo (docs, source, tests) must
   resolve to a matching section heading in DESIGN.md. Bare paper
   references like (2.2) and single-letter placeholders are out of
   scope (they cite the source paper / are documentation meta-text).
4. **DESIGN.md CLI flags** — same rule as (2) for DESIGN.md: flags the
   architecture doc cites (e.g. the §11 `--inject-rber` contract) must
   still be defined.
5. **README DESIGN-map completeness** — the README's "Where to read
   next" map must carry a row for every `## §` heading DESIGN.md
   actually has, so new sections cannot land undocumented on the
   front page.

Run:  python scripts/check_docs.py
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SRC_SUFFIXES = (".py", ".sh", ".md", ".toml", ".txt", ".yml", ".json")


def fail(errors: list) -> None:
    if errors:
        for e in errors:
            print(f"DOCS ERROR: {e}")
        sys.exit(1)


def _candidate_paths(text: str):
    """Path-looking tokens from backticks and fenced code blocks."""
    tokens = set(re.findall(r"`([^`\n]+)`", text))
    for block in re.findall(r"```(?:\w*\n)?(.*?)```", text, re.S):
        tokens.update(block.split())
    for tok in tokens:
        tok = tok.strip().rstrip(",.;:")
        if tok.startswith(("--", "-m", "http")) or "=" in tok or "$" in tok:
            continue
        if "/" in tok or tok.endswith(SRC_SUFFIXES):
            # strip trailing qualifiers like `file.py::func` or `§N`
            tok = tok.split("::")[0].split(" ")[0]
            if re.fullmatch(r"[\w./-]+", tok) and "." in tok.split("/")[-1]:
                yield tok


def check_readme_paths(errors: list) -> None:
    text = (ROOT / "README.md").read_text()
    for tok in sorted(set(_candidate_paths(text))):
        if tok.startswith("/"):           # absolute output paths (/tmp/...)
            continue
        if not (ROOT / tok).exists():
            errors.append(f"README.md references missing file: {tok}")


def _defined_flags() -> set:
    defined = set()
    for path in list((ROOT / "src" / "repro" / "launch").glob("*.py")) \
            + list((ROOT / "benchmarks").glob("*.py")) \
            + list((ROOT / "experiments").glob("*.py")) \
            + list(ROOT.glob("*.py")):
        defined.update(re.findall(r"add_argument\(\s*\"(--[a-z0-9-]+)\"",
                                  path.read_text()))
    return defined


def _check_doc_flags(doc: str, errors: list) -> None:
    text = (ROOT / doc).read_text()
    flags = set(re.findall(r"(--[a-z][a-z0-9-]+)", text))
    defined = _defined_flags()
    for flag in sorted(flags - defined):
        if flag in ("--json", "--help"):  # runner/argparse built-ins
            defined_runner = any(
                flag in p.read_text() for p in (ROOT / "benchmarks").glob("*.py"))
            if flag == "--help" or defined_runner:
                continue
        errors.append(f"{doc} documents unknown CLI flag: {flag}")


def check_readme_flags(errors: list) -> None:
    _check_doc_flags("README.md", errors)


def check_design_flags(errors: list) -> None:
    _check_doc_flags("DESIGN.md", errors)


def check_readme_design_map(errors: list) -> None:
    design = (ROOT / "DESIGN.md").read_text()
    readme = (ROOT / "README.md").read_text()
    for heading in re.findall(r"^## (§[\w-]+)", design, re.M):
        if not re.search(rf"^\|\s*{re.escape(heading)}\s*\|", readme, re.M):
            errors.append(f"README.md DESIGN map has no row for DESIGN.md "
                          f"heading '{heading}'")


def check_design_sections(errors: list) -> None:
    design = (ROOT / "DESIGN.md").read_text()
    headings = set(re.findall(r"^## (§[\w-]+)", design, re.M))
    if not headings:
        errors.append("DESIGN.md has no '## §' headings at all")
    # numbered sections (§6) or named sections (§Arch-applicability);
    # single capital letters (§N, §X) are placeholder meta-text, skipped
    ref_re = re.compile(r"DESIGN\.md\s+(§(?:\d+|[A-Z][\w-]+))")
    refs = []
    for path in ROOT.rglob("*"):
        if path.suffix not in (".py", ".md", ".sh") or not path.is_file():
            continue
        if any(part.startswith(".") for part in path.parts):
            continue
        for m in ref_re.finditer(path.read_text()):
            refs.append((path.relative_to(ROOT), m.group(1)))
    for where, ref in refs:
        if ref not in headings:
            errors.append(f"{where}: reference '{ref}' has no matching "
                          f"'## {ref}' heading in DESIGN.md "
                          f"(headings: {sorted(headings)})")


def main() -> None:
    errors: list = []
    check_readme_paths(errors)
    check_readme_flags(errors)
    check_design_sections(errors)
    check_design_flags(errors)
    check_readme_design_map(errors)
    fail(errors)
    print("docs OK: README file/flag references, DESIGN.md § "
          "cross-references/flags, and the README DESIGN map all resolve")


if __name__ == "__main__":
    main()
