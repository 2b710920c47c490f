"""``gordo lint`` / ``make lint`` entry point: run every checker, apply
the baseline, print ``file:line severity checker message`` findings.

Pure stdlib and import-light on purpose — the gate must run in seconds,
before any jax import could slow it down. Exit status: 0 = clean (no
non-baselined findings), 1 = findings, 2 = usage error.

Two-phase shape so the scan parallelizes: per-file checkers run in a
:func:`_scan_one` worker (``--jobs N`` fans files over processes; the
default ``--jobs 1`` stays in-process and deterministic), returning
findings + the cross-file EVIDENCE (knob mentions, wire-contract
producer/consumer sites, fault-seam references). The aggregate half —
wire finalize, fault finalize, stale knobs, README knob table — joins
the evidence single-threaded. Per-checker wall time is accumulated
either way and reported in the summary line (``--format json`` for CI).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from . import (
    exception_hygiene,
    fault_coverage,
    guarded_state,
    knob_registry,
    knobs,
    lock_discipline,
    metrics_conventions,
    span_seam,
    wire_contracts,
)
from .astscan import parse_module
from .findings import Baseline, Finding

# checker -> repo-relative path prefixes it runs over
SCOPES: Dict[str, Tuple[str, ...]] = {
    "lock-discipline": ("gordo_components_tpu/",),
    "guarded-state": ("gordo_components_tpu/",),
    "span-seam": (
        "gordo_components_tpu/server/",
        "gordo_components_tpu/client/",
        "gordo_components_tpu/router/",
        "gordo_components_tpu/watchman/",
    ),
    "metrics-conventions": ("gordo_components_tpu/", "tools/"),
    "knob-registry": ("gordo_components_tpu/", "tools/", "tests/"),
    # tests legitimately swallow in teardown helpers; the hygiene rule
    # covers the shipped tree
    "exception-hygiene": ("gordo_components_tpu/", "tools/"),
    "wire-contracts": ("gordo_components_tpu/", "tools/"),
    "fault-coverage": ("gordo_components_tpu/", "tools/", "tests/"),
}

KNOB_TABLE_BEGIN = "<!-- knob-table:begin (generated: make lint) -->"
KNOB_TABLE_END = "<!-- knob-table:end -->"


def repo_root(start: Optional[str] = None) -> str:
    """The checkout root: the directory holding gordo_components_tpu/."""
    here = os.path.abspath(start or os.path.dirname(__file__))
    probe = here
    while True:
        if os.path.isdir(os.path.join(probe, "gordo_components_tpu")):
            return probe
        parent = os.path.dirname(probe)
        if parent == probe:
            return os.path.abspath(start or os.getcwd())
        probe = parent


def _iter_files(root: str) -> List[str]:
    out: List[str] = []
    for prefix in ("gordo_components_tpu", "tools", "tests"):
        base = os.path.join(root, prefix)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [
                d for d in dirnames
                # lint_corpus: seeded-BAD snippets the analysis tests
                # feed the checkers directly — not part of the tree gate
                if d not in ("__pycache__", ".jax_compilation_cache",
                             "lint_corpus")
            ]
            for filename in sorted(filenames):
                if filename.endswith(".py"):
                    out.append(os.path.join(dirpath, filename))
    return out


def _in_scope(relpath: str, checker: str) -> bool:
    return relpath.startswith(SCOPES[checker]) or relpath in SCOPES[checker]


def _check_knob_table(root: str) -> List[Finding]:
    """README's knob table must equal the generated one."""
    readme = os.path.join(root, "README.md")
    try:
        with open(readme, "r", encoding="utf-8") as handle:
            text = handle.read()
    except OSError:
        return []
    begin = text.find(KNOB_TABLE_BEGIN)
    end = text.find(KNOB_TABLE_END)
    if begin == -1 or end == -1:
        return [
            Finding(
                checker="knob-registry", code="readme-table-missing",
                file="README.md", line=1, key="knob-table",
                message=(
                    "README.md has no generated knob-table block "
                    f"({KNOB_TABLE_BEGIN} ... {KNOB_TABLE_END})"
                ),
                hint="run: python -m gordo_components_tpu.analysis "
                     "--write-knob-table",
            )
        ]
    current = text[begin + len(KNOB_TABLE_BEGIN):end].strip()
    expected = knobs.render_markdown_table().strip()
    if current != expected:
        line = text[:begin].count("\n") + 1
        return [
            Finding(
                checker="knob-registry", code="readme-table-drift",
                file="README.md", line=line, key="knob-table",
                message=(
                    "README knob table differs from the registry in "
                    "analysis/knobs.py — docs drifted"
                ),
                hint="run: python -m gordo_components_tpu.analysis "
                     "--write-knob-table",
            )
        ]
    return []


def write_knob_table(root: str) -> bool:
    """Rewrite README's generated knob-table block in place."""
    readme = os.path.join(root, "README.md")
    with open(readme, "r", encoding="utf-8") as handle:
        text = handle.read()
    begin = text.find(KNOB_TABLE_BEGIN)
    end = text.find(KNOB_TABLE_END)
    if begin == -1 or end == -1:
        return False
    rendered = (
        text[: begin + len(KNOB_TABLE_BEGIN)]
        + "\n"
        + knobs.render_markdown_table()
        + "\n"
        + text[end:]
    )
    with open(readme, "w", encoding="utf-8") as handle:
        handle.write(rendered)
    return True


# -- per-file scan (the parallelizable half) ----------------------------------

# (checker name, check callable) for the simple per-file checkers
_PER_FILE = (
    ("lock-discipline", lock_discipline.check),
    ("guarded-state", guarded_state.check),
    ("span-seam", span_seam.check),
    ("metrics-conventions", metrics_conventions.check),
    ("exception-hygiene", exception_hygiene.check),
)


def _scan_one(job: Tuple[str, str]) -> Dict[str, Any]:
    """Worker: parse one file, run every in-scope per-file checker, and
    collect the cross-file evidence. Returns only picklable data so
    ``--jobs N`` can fan it across processes."""
    path, relpath = job
    result: Dict[str, Any] = {
        "findings": [], "knob_mentions": set(), "wire": None,
        "fault": None, "timings": {},
    }
    module = parse_module(path, relpath)
    if module is None:
        result["findings"].append(
            Finding(
                checker="lint", code="unparseable", file=relpath,
                line=1, key=relpath,
                message="file does not parse; checkers skipped it",
            )
        )
        return result
    timings: Dict[str, float] = result["timings"]
    for checker, check in _PER_FILE:
        if _in_scope(relpath, checker):
            started = time.perf_counter()
            result["findings"].extend(check(module))
            timings[checker] = (
                timings.get(checker, 0.0) + time.perf_counter() - started
            )
    if _in_scope(relpath, "knob-registry") and (
        relpath != "gordo_components_tpu/analysis/knobs.py"
    ):
        # knobs.py itself is the registry: its literals would make
        # every registered knob count as "mentioned" (circular
        # staleness) and can never be unregistered
        started = time.perf_counter()
        result["findings"].extend(knob_registry.check(module))
        result["knob_mentions"] = knob_registry.collect_mentions(module)
        timings["knob-registry"] = (
            timings.get("knob-registry", 0.0)
            + time.perf_counter() - started
        )
    if _in_scope(relpath, "wire-contracts") and not relpath.startswith(
        "gordo_components_tpu/analysis/"
    ):
        # the registry module's own docstrings/specs are not evidence
        started = time.perf_counter()
        wire_findings, wire_evidence = wire_contracts.scan(module)
        result["findings"].extend(wire_findings)
        result["wire"] = wire_evidence
        timings["wire-contracts"] = (
            timings.get("wire-contracts", 0.0)
            + time.perf_counter() - started
        )
    if _in_scope(relpath, "fault-coverage") and not relpath.startswith(
        "gordo_components_tpu/analysis/"
    ):
        started = time.perf_counter()
        result["fault"] = fault_coverage.scan(module)
        timings["fault-coverage"] = (
            timings.get("fault-coverage", 0.0)
            + time.perf_counter() - started
        )
    return result


def run_lint(
    root: Optional[str] = None,
    jobs: int = 1,
    timings: Optional[Dict[str, float]] = None,
) -> List[Finding]:
    root = root or repo_root()
    if timings is None:
        timings = {}
    job_list = [
        (path, os.path.relpath(path, root).replace(os.sep, "/"))
        for path in _iter_files(root)
    ]
    if jobs == 0:
        jobs = os.cpu_count() or 1
    if jobs > 1:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        # spawn, not fork: run_lint is also called in-process by the
        # test suite, where jax has already spun up worker threads —
        # forking a multithreaded process can deadlock in the child.
        # The analysis package imports in ~0.3s, so spawn stays cheap.
        with ProcessPoolExecutor(
            max_workers=jobs,
            mp_context=multiprocessing.get_context("spawn"),
        ) as pool:
            results = list(pool.map(_scan_one, job_list, chunksize=8))
    else:
        results = [_scan_one(job) for job in job_list]

    findings: List[Finding] = []
    mentions = set()
    wire_evidence = []
    fault_evidence = []
    for result in results:
        findings.extend(result["findings"])
        mentions |= result["knob_mentions"]
        if result["wire"] is not None:
            wire_evidence.append(result["wire"])
        if result["fault"] is not None:
            fault_evidence.append(result["fault"])
        for checker, spent in result["timings"].items():
            timings[checker] = timings.get(checker, 0.0) + spent

    started = time.perf_counter()
    findings.extend(wire_contracts.finalize(wire_evidence))
    timings["wire-contracts"] = (
        timings.get("wire-contracts", 0.0) + time.perf_counter() - started
    )
    started = time.perf_counter()
    findings.extend(fault_coverage.finalize(fault_evidence))
    timings["fault-coverage"] = (
        timings.get("fault-coverage", 0.0) + time.perf_counter() - started
    )

    started = time.perf_counter()
    # registered-but-unmentioned knobs. README PROSE counts as a
    # mention, but the generated knob-table block must NOT: it always
    # contains every registered knob (it is rendered FROM the
    # registry), so counting it would make the stale check circular
    # and dead knobs would live forever.
    readme = os.path.join(root, "README.md")
    try:
        with open(readme, "r", encoding="utf-8") as handle:
            readme_text = handle.read()
    except OSError:
        readme_text = ""
    begin = readme_text.find(KNOB_TABLE_BEGIN)
    end = readme_text.find(KNOB_TABLE_END)
    if begin != -1 and end != -1:
        readme_text = readme_text[:begin] + readme_text[end:]
    # word-bounded: prose naming GORDO_COMPILE_CACHE_STORE must not
    # also count as a mention of its prefix GORDO_COMPILE_CACHE
    readme_mentions = set(knob_registry._KNOB_RE.findall(readme_text))
    findings.extend(
        knob_registry.stale_knobs(set(mentions) | readme_mentions)
    )
    findings.extend(_check_knob_table(root))
    timings["knob-registry"] = (
        timings.get("knob-registry", 0.0) + time.perf_counter() - started
    )
    return findings


def _render_timings(timings: Dict[str, float]) -> str:
    return ", ".join(
        f"{checker} {spent:.2f}s"
        for checker, spent in sorted(
            timings.items(), key=lambda item: -item[1]
        )
    )


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="gordo lint",
        description=(
            "Invariant linter: lock discipline, guarded state, span "
            "seams, wire contracts, fault-seam coverage, exception "
            "hygiene, metric conventions, knob registry "
            "(docs/ARCHITECTURE.md §17/§21)."
        ),
    )
    parser.add_argument("--root", default=None,
                        help="checkout root (default: auto-detect)")
    parser.add_argument("--baseline", default=None,
                        help="baseline path (default: <root>/lint_baseline"
                             ".json)")
    parser.add_argument("--jobs", type=int, default=1, metavar="N",
                        help="parallel per-file scan processes "
                             "(0 = one per CPU; default 1, in-process)")
    parser.add_argument("--format", choices=("text", "json"),
                        default="text",
                        help="output format (json: one object with "
                             "findings/baselined/timings, CI-friendly)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="grandfather every current finding into the "
                             "baseline (reasons start as TODO — fill them "
                             "in; a TODO-stubbed entry is itself reported "
                             "as baseline[unjustified-keep] until a real "
                             "reason lands)")
    parser.add_argument("--write-knob-table", action="store_true",
                        help="regenerate README.md's knob table from "
                             "analysis/knobs.py and exit")
    parser.add_argument("--show-baselined", action="store_true",
                        help="also print findings the baseline suppresses")
    args = parser.parse_args(argv)

    root = os.path.abspath(args.root) if args.root else repo_root()
    if args.write_knob_table:
        if not write_knob_table(root):
            print("README.md has no knob-table markers", file=sys.stderr)
            return 2
        print("README.md knob table regenerated")
        return 0

    started = time.perf_counter()
    timings: Dict[str, float] = {}
    findings = run_lint(root, jobs=args.jobs, timings=timings)
    baseline_path = args.baseline or os.path.join(root, "lint_baseline.json")
    baseline = Baseline.load(baseline_path)

    if args.write_baseline:
        # rebuild from CURRENT findings: existing reasons survive, new
        # findings start as TODO, and entries whose violation is gone
        # are pruned — a freshly written baseline always gates clean
        baseline.entries = {
            finding.ident: baseline.entries.get(
                finding.ident, "TODO: justify"
            )
            for finding in findings
        }
        baseline.save(baseline_path)
        print(f"baseline written: {len(baseline.entries)} entr(ies) in "
              f"{baseline_path}")
        return 0

    fresh, suppressed = baseline.split(findings)
    fresh.sort(key=lambda f: (f.file, f.line, f.checker, f.code))
    elapsed = time.perf_counter() - started

    if args.format == "json":
        def _as_dict(finding: Finding) -> Dict[str, Any]:
            return {
                "file": finding.file, "line": finding.line,
                "severity": finding.severity, "checker": finding.checker,
                "code": finding.code, "key": finding.key,
                "message": finding.message, "hint": finding.hint,
                "ident": finding.ident,
            }

        print(json.dumps(
            {
                "findings": [_as_dict(f) for f in fresh],
                "baselined": [
                    dict(_as_dict(f),
                         reason=baseline.entries.get(f.ident, ""))
                    for f in suppressed
                ],
                "timings": {
                    checker: round(spent, 4)
                    for checker, spent in sorted(timings.items())
                },
                "elapsed": round(elapsed, 4),
                "clean": not fresh,
            },
            indent=2,
        ))
        return 1 if fresh else 0

    for finding in fresh:
        print(finding.render())
    if args.show_baselined and suppressed:
        print(f"-- {len(suppressed)} baselined finding(s):")
        for finding in suppressed:
            print(f"   {finding.render()}  "
                  f"[baseline: {baseline.entries.get(finding.ident, '')}]")
    print(
        f"lint: {len(fresh)} finding(s), {len(suppressed)} baselined, "
        f"{elapsed:.2f}s [{_render_timings(timings)}]"
    )
    return 1 if fresh else 0
