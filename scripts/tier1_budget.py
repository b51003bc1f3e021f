#!/usr/bin/env python
"""Tier-1 gate wall-clock budget report from pytest ``--durations`` output.

The driver runs the whole not-slow suite under ``timeout -k 10 1470`` with
six xdist workers (``-n 6 --dist loadfile``; the command is in
``/root/TESTS_LAST_RUN.json``) and took 374 s of it at PR 30.  The slow
lane was sized for an earlier serial 1080 s wall: which marks earn a place
back is a measurement this script reports (ROADMAP.md D14), from a log of
the gate run with ``--durations``:

    # from a saved log (the gate already tees /tmp/_t1.log):
    python -m pytest tests/ -q -m 'not slow' -n 6 --dist loadfile \
        --durations=50 2>&1 | tee /tmp/_t1.log
    python scripts/tier1_budget.py /tmp/_t1.log

    # or pipe it:
    python scripts/tier1_budget.py - < /tmp/_t1.log

    # or let the script run pytest itself (slow — the full gate):
    python scripts/tier1_budget.py --run

It parses the ``slowest N durations`` table (``12.34s call
tests/x.py::test_y`` lines), merges the setup/call/teardown phases per
test, and prints:

- the top-N tests by total wall (``--top``, default 15) with their
  phase split and share of the measured wall;
- per-file subtotals (the "which module is the problem" view);
- the projected gate wall vs the timeout: pytest's own ``in N.NNs``
  summary when present (that IS the gate wall), else the durations sum
  (a lower bound — pytest only reports the slowest N phases).

Exit status: 0 when the projected wall fits inside the budget scaled by
``--headroom`` (default 0.85: the last 15 % absorbs a loaded machine), 2
when it does not, 1 on a parse error.  Under xdist the durations sum is work
summed over the workers, not wall time: trust the projection only when the
log carries pytest's tail summary.
No dependencies beyond the standard library; the report is plain text so
it can ride in a PR description verbatim.
"""

from __future__ import annotations

import argparse
import re
import subprocess
import sys

BUDGET_S = 1470.0  # the driver's `timeout -k 10 1470` wall

# "12.34s call     tests/test_x.py::test_y[param]"
_DUR_RE = re.compile(
    r"^\s*(?P<secs>\d+(?:\.\d+)?)s\s+"
    r"(?P<phase>setup|call|teardown)\s+"
    r"(?P<test>\S+)\s*$")
# pytest's tail summary: "123 passed, 4 failed, ... in 456.78s"
_WALL_RE = re.compile(r"\bin (?P<secs>\d+(?:\.\d+)?)s\b")


def parse_durations(lines) -> tuple[dict, float | None]:
    """``{test_id: {phase: secs}}`` plus the suite wall from the tail
    summary (None when the log has no ``in N.NNs`` line)."""
    tests: dict = {}
    wall = None
    for line in lines:
        m = _DUR_RE.match(line)
        if m:
            phases = tests.setdefault(m.group("test"), {})
            phases[m.group("phase")] = (phases.get(m.group("phase"), 0.0)
                                        + float(m.group("secs")))
            continue
        m = _WALL_RE.search(line)
        if m:
            wall = float(m.group("secs"))  # last one wins (re-runs)
    return tests, wall


def _fmt_row(name, total, phases, share):
    split = "/".join(f"{phases.get(p, 0.0):.1f}"
                     for p in ("setup", "call", "teardown"))
    return f"{total:8.1f}s  {share:5.1%}  [{split}]  {name}"


def report(tests: dict, wall, top: int, budget: float,
           headroom: float, out=sys.stdout) -> int:
    if not tests:
        print("no `--durations` table found — rerun pytest with "
              "--durations=50 (or higher)", file=sys.stderr)
        return 1
    totals = {t: sum(p.values()) for t, p in tests.items()}
    measured = sum(totals.values())
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])

    print(f"tier-1 budget report — {len(tests)} tests in the durations "
          f"table, {measured:.1f}s measured", file=out)
    print(f"\ntop {min(top, len(ranked))} by wall "
          "(total  share  [setup/call/teardown]):", file=out)
    for name, total in ranked[:top]:
        print(_fmt_row(name, total, tests[name],
                       total / measured if measured else 0.0), file=out)

    by_file: dict = {}
    for name, total in totals.items():
        by_file[name.split("::", 1)[0]] = (
            by_file.get(name.split("::", 1)[0], 0.0) + total)
    print("\nper-file subtotals:", file=out)
    for path, total in sorted(by_file.items(), key=lambda kv: -kv[1]):
        print(f"{total:8.1f}s  {path}", file=out)

    projected = wall if wall is not None else measured
    basis = ("suite wall (pytest tail summary)" if wall is not None
             else "durations sum — LOWER BOUND, pytest reports only the "
                  "slowest phases; rerun with a larger --durations for a "
                  "tighter floor")
    limit = budget * headroom
    verdict = "OK" if projected <= limit else "OVER"
    print(f"\nprojected gate wall: {projected:.1f}s of {budget:.0f}s "
          f"({projected / budget:.1%} of the timeout; basis: {basis})",
          file=out)
    print(f"headroom target: <= {limit:.0f}s "
          f"({headroom:.0%} of budget) -> {verdict}", file=out)
    if verdict == "OVER":
        over = projected - limit
        print(f"move ~{over:.0f}s of tests to the slow lane "
              "(@pytest.mark.slow) — start from the top of the table",
              file=out)
    return 0 if verdict == "OK" else 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Tier-1 gate wall-clock budget report from pytest "
                    "--durations output")
    ap.add_argument("log", nargs="?", default=None,
                    help="pytest log file to parse ('-' = stdin); "
                         "omit with --run")
    ap.add_argument("--run", action="store_true",
                    help="run the tier-1 gate command itself "
                         "(JAX_PLATFORMS=cpu, --durations) and parse "
                         "its output")
    ap.add_argument("--top", type=int, default=15,
                    help="rows in the slowest-tests table (default 15)")
    ap.add_argument("--durations", type=int, default=50,
                    help="--durations value for --run (default 50)")
    ap.add_argument("--budget", type=float, default=BUDGET_S,
                    help=f"gate timeout, seconds (default {BUDGET_S:.0f})")
    ap.add_argument("--headroom", type=float, default=0.85,
                    help="pass threshold as a fraction of budget "
                         "(default 0.85)")
    args = ap.parse_args(argv)

    if args.run:
        import os
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/", "-q", "-m",
             "not slow", "--continue-on-collection-errors",
             "-n", "6", "--dist", "loadfile",
             f"--durations={args.durations}", "-p", "no:cacheprovider"],
            capture_output=True, text=True, env=env)
        lines = (proc.stdout + proc.stderr).splitlines()
    elif args.log is None:
        ap.error("either a log file (or '-') or --run is required")
    elif args.log == "-":
        lines = sys.stdin.read().splitlines()
    else:
        with open(args.log) as f:
            lines = f.read().splitlines()

    tests, wall = parse_durations(lines)
    return report(tests, wall, args.top, args.budget, args.headroom)


if __name__ == "__main__":
    sys.exit(main())
