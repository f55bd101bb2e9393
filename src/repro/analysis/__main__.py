"""CLI: lint the shipped compiled programs + library source.

    python -m repro.analysis                      # everything
    python -m repro.analysis --programs static-flat,fleet-flat
    python -m repro.analysis --source-only        # AST lint only
    python -m repro.analysis --json report.json   # CI artifact

Exit status 1 iff any ERROR-severity finding — the CI gate
(ci_check.sh --lint, .github/workflows/ci.yml lint job).
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

from repro.analysis import (Severity, analyze_program, available_programs,
                            build_programs, lint_source, report_json,
                            summarize)
from repro.analysis.registry import PROGRAMS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static privacy/perf sanitizer for the compiled "
                    "DWFL programs")
    ap.add_argument("--programs", default=None,
                    help="comma-separated registry subset "
                         f"(default: all of {','.join(PROGRAMS)})")
    ap.add_argument("--source-only", action="store_true",
                    help="run only the AST source lint (no tracing)")
    ap.add_argument("--no-source", action="store_true",
                    help="skip the AST source lint")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the JSON report here (CI artifact)")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="print only non-INFO findings and the summary")
    args = ap.parse_args(argv)

    t0 = time.time()
    findings, programs = [], []
    if not args.source_only:
        if args.programs:
            names = args.programs.split(",")
        else:
            names = available_programs()
            for skipped in set(PROGRAMS) - set(names):
                print(f"[analysis] {skipped}: skipped (environment "
                      f"precondition not met — e.g. too few devices)")
        for name in names:
            t1 = time.time()
            prog, = build_programs([name])   # trace + donated compile
            fs = analyze_program(prog)
            findings.extend(fs)
            programs.append(prog.name)
            print(f"[analysis] {prog.name}: {len(fs)} findings "
                  f"({time.time() - t1:.1f}s)")
    if not args.no_source:
        findings.extend(lint_source())
        programs.append("source")

    for f in findings:
        if not (args.quiet and f.severity == Severity.INFO):
            print(f)
    summary = summarize(findings)
    elapsed = time.time() - t0
    print(f"[analysis] {len(programs)} targets, {summary['error']} error / "
          f"{summary['warning']} warning / {summary['info']} info "
          f"({elapsed:.1f}s)")

    if args.json:
        out = pathlib.Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(report_json(
            findings, programs,
            meta={"elapsed_s": round(elapsed, 1), "argv": list(argv or [])}))
        print(f"[analysis] report -> {out}")
    return 1 if summary["error"] else 0


if __name__ == "__main__":
    sys.exit(main())
