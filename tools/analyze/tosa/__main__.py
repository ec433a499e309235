"""CLI for the tosa analyzer: ``python -m tosa [targets...]``.

Exit status is 0 when every finding is either inline-suppressed or
covered by the baseline, 1 when unsuppressed findings remain, 2 on usage
errors — so ``python -m tosa`` works directly as a CI gate.

Output modes: human (default), ``--json``, ``--sarif`` (SARIF 2.1.0);
``--out`` / ``--sarif-out`` additionally write the JSON / SARIF reports
to files, so one run can emit both artifacts. ``--changed FILE...``
restricts *per-file* findings to the named files while still indexing the
default corpus, which is what the pre-commit wrapper uses; the phase-1
index cache (on by default, ``--no-cache`` to disable) makes that fast.
"""

import argparse
import json
import os
import sys

from . import __version__, core, sarif
from .checkers import ALL_CHECKERS, make_checkers

#: what a bare ``python -m tosa`` analyzes, relative to the repo root
DEFAULT_TARGETS = ("tensorflowonspark_tpu", "scripts")

BASELINE_RELPATH = os.path.join("tools", "analyze", "baseline.json")

#: phase-1 index cache, relative to the repo root (gitignored)
CACHE_RELPATH = os.path.join("tools", "analyze", ".tosa_cache.json")


def find_root(start):
    """Walk up from ``start`` to the repo root (pyproject.toml or .git)."""
    cur = os.path.abspath(start)
    while True:
        if os.path.isfile(os.path.join(cur, "pyproject.toml")) or os.path.isdir(
            os.path.join(cur, ".git")
        ):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.path.abspath(start)
        cur = parent


def build_parser():
    p = argparse.ArgumentParser(
        prog="python -m tosa",
        description="AST-based invariant analyzer for tensorflowonspark_tpu",
    )
    p.add_argument(
        "targets",
        nargs="*",
        help="files or directories to analyze (default: {})".format(
            ", ".join(DEFAULT_TARGETS)
        ),
    )
    p.add_argument(
        "--rules",
        help="comma-separated rule ids to run (default: all)",
    )
    p.add_argument("--json", action="store_true", help="emit a JSON report")
    p.add_argument(
        "--sarif", action="store_true", help="emit a SARIF 2.1.0 report"
    )
    p.add_argument("--out", help="also write the JSON report to this file")
    p.add_argument(
        "--sarif-out", help="also write the SARIF 2.1.0 report to this file"
    )
    p.add_argument(
        "--changed",
        action="store_true",
        help="targets are a changed-file set: report per-file findings only "
        "for them, but index the default corpus so project-wide rules "
        "still see the whole program",
    )
    p.add_argument(
        "--no-cache",
        action="store_true",
        help="disable the content-hash phase-1 index cache",
    )
    p.add_argument(
        "--jobs",
        type=int,
        default=None,
        help="phase-1 worker processes (default: min(4, cpu count); "
        "1 = serial; cache hits never spawn workers)",
    )
    p.add_argument(
        "--cache",
        help="index cache path (default: <root>/{})".format(
            CACHE_RELPATH.replace(os.sep, "/")
        ),
    )
    p.add_argument(
        "--baseline",
        help="baseline file (default: <root>/{})".format(
            BASELINE_RELPATH.replace(os.sep, "/")
        ),
    )
    p.add_argument(
        "--write-baseline",
        action="store_true",
        help="grandfather every current finding into the baseline and exit 0",
    )
    p.add_argument(
        "--list-rules", action="store_true", help="print the rule catalog"
    )
    p.add_argument(
        "--root",
        help="repo root for relative paths and default targets "
        "(default: auto-detected from cwd)",
    )
    p.add_argument(
        "--version", action="version", version="tosa {}".format(__version__)
    )
    return p


def main(argv=None):
    args = build_parser().parse_args(argv)

    if args.list_rules:
        width = max(len(r) for r in ALL_CHECKERS)
        for rule in sorted(ALL_CHECKERS):
            print("{:<{}}  {}".format(rule, width, ALL_CHECKERS[rule].description))
        return 0

    root = os.path.abspath(args.root) if args.root else find_root(os.getcwd())

    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
    try:
        checkers = make_checkers(rules)
    except KeyError as e:
        print("tosa: {}".format(e.args[0]), file=sys.stderr)
        return 2

    default_targets = [
        os.path.join(root, t)
        for t in DEFAULT_TARGETS
        if os.path.exists(os.path.join(root, t))
    ]
    report_only = None
    if args.changed:
        if not args.targets:
            print("tosa: --changed requires explicit file targets", file=sys.stderr)
            return 2
        changed_paths = core.iter_python_files(args.targets)
        report_only = {
            os.path.relpath(p, root).replace(os.sep, "/") for p in changed_paths
        }
        corpus = list(
            dict.fromkeys(core.iter_python_files(default_targets) + changed_paths)
        )
        paths = corpus
        if not changed_paths:
            print("tosa: 0 changed python files, nothing to do")
            return 0
    else:
        targets = args.targets or default_targets
        paths = core.iter_python_files(targets)
        if not paths:
            print(
                "tosa: no python files under: {}".format(", ".join(targets)),
                file=sys.stderr,
            )
            return 2

    cache_path = None
    if not args.no_cache:
        cache_path = args.cache or os.path.join(root, CACHE_RELPATH)
        if not os.path.isdir(os.path.dirname(cache_path)):
            cache_path = None

    jobs = args.jobs if args.jobs and args.jobs > 0 else min(4, os.cpu_count() or 1)
    findings = core.analyze_project(
        paths, checkers, root=root, cache_path=cache_path, report_only=report_only,
        jobs=jobs,
    )

    baseline_path = args.baseline or os.path.join(root, BASELINE_RELPATH)
    if args.write_baseline:
        core.write_baseline(baseline_path, findings)
        print(
            "tosa: wrote {} fingerprint(s) to {}".format(
                len([f for f in findings if f.suppressed is None]),
                os.path.relpath(baseline_path, root),
            )
        )
        return 0

    findings = core.apply_baseline(findings, core.load_baseline(baseline_path))
    gate = core.gating(findings)

    json_report = {
        "version": __version__,
        "rules": sorted(c.rule for c in checkers),
        "files_analyzed": len(paths),
        "findings": [f.to_dict() for f in findings],
        "gating": len(gate),
    }
    sarif_report = None
    if args.sarif or args.sarif_out:
        sarif_report = sarif.to_sarif(findings, checkers, __version__)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(json_report, f, indent=2, sort_keys=True)
            f.write("\n")
    if args.sarif_out:
        with open(args.sarif_out, "w", encoding="utf-8") as f:
            json.dump(sarif_report, f, indent=2, sort_keys=True)
            f.write("\n")

    if args.sarif:
        print(json.dumps(sarif_report, indent=2, sort_keys=True))
    elif args.json:
        print(json.dumps(json_report, indent=2, sort_keys=True))
    else:
        for f in findings:
            if f.suppressed is not None or f.baselined:
                continue
            print("{}:{}: [{}] {}".format(f.path, f.line, f.rule, f.message))
        suppressed = sum(1 for f in findings if f.suppressed is not None)
        baselined = sum(1 for f in findings if f.baselined)
        print(
            "tosa: {} file(s), {} finding(s) "
            "({} suppressed, {} baselined, {} gating)".format(
                len(paths), len(findings), suppressed, baselined, len(gate)
            )
        )
    return 1 if gate else 0


if __name__ == "__main__":
    sys.exit(main())
