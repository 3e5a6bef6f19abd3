"""Benchmark registry — one entry per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run             # quick set
    PYTHONPATH=src python -m benchmarks.run --full      # everything
    PYTHONPATH=src python -m benchmarks.run --only comm_cost
    PYTHONPATH=src python -m benchmarks.run --only secure_allreduce \\
        --json BENCH_secure_agg.json    # machine-readable {name: us}

``--json`` captures every CSV row whose us_per_call column parses as a
number and writes ``{name: us_per_call}`` — the perf trajectory file
future PRs diff against.

Row-naming rule: a bench row's name ends in a unit suffix that states
what the numeric column means — ``_us`` for microseconds per call
(lower is better), ``_sps`` for sessions per second (higher is
better), ``_bytes`` for wire bytes moved, and ``_pct`` for relative
overhead percentages.  Every row MUST carry a suffix: the unsuffixed
pre-PR-7 duplicates of the service rows served their one deprecation
release and are gone (PR 8).

``--guard NAME`` (repeatable) makes the run a regression gate: after
the bench, NAME's fresh value is compared against the value already
committed in the ``--json`` trajectory file, and the run exits 1 if a
higher-is-better row (``_sps``) dropped more than 10% (or a
lower-is-better ``_us`` / ``_bytes`` row rose more than 10% — wire
bytes regress upward exactly like latencies do).  The fresh value is
still merged, so an intentional regression is committed by rerunning
after review — the gate is on the DIFF, not the file.
"""
import argparse
import contextlib
import io
import json
import sys


class _Tee(io.TextIOBase):
    """Mirror writes to the real stdout while buffering for parsing."""

    def __init__(self, stream):
        self._stream = stream
        self._buf = io.StringIO()

    def write(self, s):
        self._stream.write(s)
        self._buf.write(s)
        return len(s)

    def flush(self):
        self._stream.flush()

    def getvalue(self):
        return self._buf.getvalue()


def parse_rows(text: str) -> dict:
    """CSV rows 'name,us,derived' -> {name: us} for numeric us columns."""
    rows = {}
    for line in text.splitlines():
        parts = line.strip().split(",")
        if len(parts) < 2:
            continue
        try:
            rows[parts[0]] = float(parts[1])
        except ValueError:
            continue
    return rows


def main() -> None:
    import functools

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only")
    ap.add_argument("--json", dest="json_path",
                    help="merge {name: us_per_call} for all numeric rows "
                         "into this file (existing rows are kept)")
    ap.add_argument("--transport", choices=("sim", "mesh"), default="sim",
                    help="service bench executor transport (mesh needs "
                         "one device per protocol node)")
    ap.add_argument("--guard", action="append", default=[], metavar="NAME",
                    help="regression gate: exit 1 if this row regresses "
                         ">10%% vs its committed --json value (repeatable)")
    args = ap.parse_args()
    if args.guard and not args.json_path:
        ap.error("--guard needs --json (the committed trajectory file "
                 "to diff against)")

    from benchmarks import (comm_cost, crypto_breakdown, funcs, kernels,
                            lower_bound, secure_allreduce, service, tune)
    table = {
        "comm_cost": comm_cost.run,                # paper Fig 3a/3b
        "crypto_breakdown": crypto_breakdown.run,  # paper Fig 3c/3d
        "lower_bound": lower_bound.run,            # paper Thm 1
        "secure_allreduce": secure_allreduce.run,  # tensor-scale schedules
        "kernels": kernels.run,                    # pallas kernel microbench
        "service": functools.partial(              # multi-session load gen
            service.run, transport=args.transport),
        "tune": tune.run,                          # tuner decisions + gate
        "funcs": funcs.run,                        # secure-function layer
    }
    names = [args.only] if args.only else list(table)
    tee = _Tee(sys.stdout)
    ok = True
    with contextlib.redirect_stdout(tee):
        print("name,us_per_call,derived")
        for n in names:
            try:
                table[n](full=args.full)
            except Exception as e:  # pragma: no cover
                ok = False
                print(f"{n},ERROR,{e!r}")
    if args.json_path:
        rows = {}
        try:   # append/update semantics: earlier lanes' rows are kept
            with open(args.json_path) as f:
                rows = json.load(f)
        except (OSError, ValueError):
            pass
        committed = dict(rows)
        fresh = parse_rows(tee.getvalue())
        rows.update(fresh)
        with open(args.json_path, "w") as f:
            json.dump(rows, f, indent=2, sort_keys=True)
            f.write("\n")
        for name in args.guard:
            if name not in fresh:
                print(f"GUARD {name}: row not produced by this run",
                      file=sys.stderr)
                ok = False
                continue
            base = committed.get(name)
            if base is None or base == 0:
                print(f"GUARD {name}: no committed baseline, "
                      f"recorded {fresh[name]:.0f}", file=sys.stderr)
                continue
            # higher-is-better unless the unit suffix says microseconds
            # or wire bytes (both regress upward)
            lower_is_better = name.endswith(("_us", "_bytes"))
            ratio = (base / fresh[name] if lower_is_better
                     else fresh[name] / base)
            verdict = "OK" if ratio >= 0.9 else "REGRESSION"
            print(f"GUARD {name}: {base:.0f} -> {fresh[name]:.0f} "
                  f"({ratio:.2f}x) {verdict}", file=sys.stderr)
            if ratio < 0.9:
                ok = False
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
