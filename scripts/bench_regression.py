#!/usr/bin/env python
"""Bench-regression sentinel over ``tuning/BENCH_HISTORY.jsonl``.

Every ``bench.py`` run/sweep appends one record to the history
(``tmlibrary_tpu.tuning.append_bench_history``); this script judges the
latest record against the best comparable one — same (metric, config,
backend class) — and exits with a pinned, CI-gateable code:

  0  ok / improvement
  1  regression beyond ``--threshold`` (outranks staleness)
  2  latest record is older than ``--stale-hours``
  3  no comparable baseline to judge against

``--baseline FILE`` compares against a committed baseline history instead
of earlier in-history records (the CI CPU smoke uses this: a fresh
ephemeral history judged against ``tuning/BENCH_CPU_BASELINE.jsonl``).
On regression or staleness the verdict's re-capture labels
(``bench:<config>`` / ``sweep:<config>``) are merged into
``tuning/RECAPTURE.json`` — unless ``--no-queue`` — the list of what to
measure again on the chip.

Usage:
  python scripts/bench_regression.py                      # whole history
  python scripts/bench_regression.py --config 3           # one config
  python scripts/bench_regression.py --history /tmp/h.jsonl \
      --baseline tuning/BENCH_CPU_BASELINE.jsonl --threshold 0.5
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tmlibrary_tpu import perf, tuning  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--history", default=None,
                        help="history file (default tuning/BENCH_HISTORY"
                             ".jsonl, BENCH_HISTORY env)")
    parser.add_argument("--baseline", default=None,
                        help="judge against this history file instead of "
                             "earlier in-history records")
    parser.add_argument("--config", default=None,
                        help="restrict to one bench config")
    parser.add_argument("--metric", default=None,
                        help="restrict to one metric")
    parser.add_argument("--threshold", type=float, default=0.05,
                        help="regression fraction vs the best baseline "
                             "(default 0.05)")
    parser.add_argument("--stale-hours", type=float, default=None,
                        dest="stale_hours",
                        help="staleness budget in hours (default "
                             "BENCH_STALE_HOURS or 72)")
    parser.add_argument("--queue-out", default=None,
                        help="re-capture queue file (default "
                             "tuning/RECAPTURE.json, WATCH_RECAPTURE env)")
    parser.add_argument("--no-queue", action="store_true",
                        help="report only; do not write re-capture items")
    parser.add_argument("--json", action="store_true", dest="as_json",
                        help="emit the verdict as JSON")
    args = parser.parse_args(argv)

    history_path = args.history or tuning.bench_history_path()
    history = tuning.load_bench_history(history_path)
    if not history:
        # absent or empty history is a fresh checkout, not an error:
        # report the pinned no-baseline verdict with a hint instead of a
        # confusing "no comparable records" over a file that isn't there
        state = ("absent" if not os.path.exists(history_path) else "empty")
        verdict = {"status": "no_baseline", "exit_code": perf.EXIT_NO_BASELINE,
                   "reason": f"bench history {history_path} is {state} — "
                             "run bench.py on the chip to "
                             "capture a first record",
                   "history_path": str(history_path), "history_records": 0,
                   "latest": None, "baseline": None, "delta_frac": None,
                   "age_hours": None, "recapture": []}
        if args.as_json:
            print(json.dumps(verdict, indent=2))
        else:
            print(f"bench_regression: {verdict['status']} "
                  f"(exit {verdict['exit_code']})")
            print(f"  reason: {verdict['reason']}")
        return verdict["exit_code"]
    baseline = None
    if args.baseline:
        baseline = tuning.load_bench_history(args.baseline)
        if not baseline:
            print(f"bench_regression: baseline {args.baseline} is empty or "
                  "unreadable", file=sys.stderr)
    verdict = perf.compare_history(
        history,
        baseline=baseline,
        config=args.config,
        metric=args.metric,
        threshold=args.threshold,
        stale_hours=args.stale_hours if args.stale_hours is not None
        else perf.stale_hours(),
    )

    if verdict["recapture"] and not args.no_queue:
        path = perf.write_recapture(
            verdict["recapture"], path=args.queue_out,
            reason=f"bench_regression: {verdict['status']}",
        )
        verdict["recapture_queue"] = path

    if args.as_json:
        print(json.dumps(verdict, indent=2))
        return verdict["exit_code"]

    latest = verdict.get("latest") or {}
    best = verdict.get("baseline") or {}
    print(f"bench_regression: {verdict['status']} "
          f"(exit {verdict['exit_code']})")
    if latest:
        print(f"  latest:   {latest.get('metric')} config="
              f"{latest.get('config')} backend={latest.get('backend')} "
              f"value={latest.get('value')}")
    if best:
        print(f"  baseline: value={best.get('value')} "
              f"(delta {verdict['delta_frac']:+.1%}, "
              f"threshold ±{args.threshold:.0%})")
    if verdict.get("age_hours") is not None:
        print(f"  age: {verdict['age_hours']}h "
              f"(stale budget {args.stale_hours or perf.stale_hours():g}h)")
    if verdict.get("reason"):
        print(f"  reason: {verdict['reason']}")
    if verdict.get("recapture"):
        queued = verdict.get("recapture_queue", "not queued (--no-queue)")
        print(f"  recapture: {', '.join(verdict['recapture'])} -> {queued}")
    return verdict["exit_code"]


if __name__ == "__main__":
    sys.exit(main())
