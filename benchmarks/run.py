"""Benchmark runner — auto-registers every benchmarks/ module with a main().

Discovery replaces the hand-kept list that drifted (fused_vs_composed and
semi_runtime were never registered): any module in this package exposing a
callable ``main() -> int`` is a benchmark. Module conventions:

  * ``SMOKE_ARGV``      — argv the module's CLI gets under ``--smoke``
    (e.g. ``["--smoke"]``, ``["--iters", "1"]``); modules without it run
    their default path in both modes.
  * ``INFORMATIONAL``   — nonzero return is reported but does not fail the
    run (e.g. roofline_table when no dry-run file exists).
  * ``METRICS``         — a dict the bench's ``main()`` fills with its
    headline numbers; ``--json-out DIR`` persists it (plus name, argv,
    return code, wall-clock, git sha) as ``DIR/BENCH_<name>.json`` — the
    perf-trajectory artifact the CI smoke gate uploads on every PR.

Determinism convention: everything in ``METRICS`` must be a pure function
of (seed, argv) — running a ``--smoke`` bench twice must reproduce it
byte-identically (regression-tested in tests/test_bench_determinism.py).
Measured wall-clock quantities are the sanctioned exception: they live
under keys named ``timing`` (any nesting level), which
``canonical_metrics`` strips alongside the runner's own volatile fields
(``seconds``, ``git_sha``) before any artifact comparison.

``python -m benchmarks.run`` runs everything and exits non-zero on any
paper-validation mismatch; ``--smoke`` runs every bench's smoke path (the
CI gate — registry drift or bench breakage fails the build);
``python -m benchmarks.run table1_taxi semi_sweep`` runs a subset.

Perf trajectory (``--compare``): each commit carries its baseline
artifacts as repo-root ``BENCH_<name>.json`` files. ``--compare`` diffs
the current run against them — deterministic metrics must agree exactly
(float tolerance), and every numeric leaf under a ``timing`` key may not
regress by more than ``--compare-threshold`` (a fraction: 5.0 == 6x
worse fails). "Worse" is direction-aware: slower for latency-style
leaves, lower for throughput-style ones (``qps``/``rate``/... in the
leaf name). Timing *improvements* and the runner's own
``seconds``/``git_sha`` never trip it. ``--update-baseline`` re-records
the repo-root artifacts — run it (and commit the result) whenever a bench
legitimately changes its metrics or argv.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import pkgutil
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)   # `python benchmarks/run.py` == `-m` form

import benchmarks  # noqa: E402


def discover(names: list | None = None) -> dict:
    """name -> module for benchmarks/ modules exposing main().

    ``names`` restricts discovery — and therefore the jax-heavy imports —
    to that subset (in the given order); unknown names abort with the full
    candidate list."""
    candidates = sorted(i.name for i in
                        pkgutil.iter_modules(benchmarks.__path__)
                        if i.name != "run")
    if names:
        unknown = [n for n in names if n not in candidates]
        if unknown:
            sys.exit(f"unknown benchmark(s) {unknown}; "
                     f"candidates: {candidates}")
    registry = {}
    for name in (names or candidates):
        mod = importlib.import_module(f"benchmarks.{name}")
        if callable(getattr(mod, "main", None)):
            registry[name] = mod
        elif names:
            sys.exit(f"{name} is a library module (no main()); "
                     f"candidates: {candidates}")
    return registry


# keys holding measured wall-clock (or equivalently volatile) values —
# excluded from artifact determinism comparisons at any nesting depth
# ("info" carries the per-run telemetry snapshot: span timings and
# counters are measurements, never compared)
VOLATILE_KEYS = frozenset({"timing", "seconds", "git_sha", "info"})


def canonical_metrics(obj, volatile: frozenset = VOLATILE_KEYS):
    """The deterministic projection of a METRICS dict / BENCH record:
    volatile keys dropped recursively, dict keys sorted — two runs of the
    same bench with the same seed+argv must serialize identically."""
    if isinstance(obj, dict):
        return {k: canonical_metrics(obj[k], volatile)
                for k in sorted(obj) if k not in volatile}
    if isinstance(obj, (list, tuple)):
        return [canonical_metrics(v, volatile) for v in obj]
    return obj


def collect_timings(obj, under_timing: bool = False,
                    prefix: str = "") -> dict:
    """path -> float for every numeric leaf under a ``timing`` key.

    The complement of ``canonical_metrics``: the measured wall-clock
    quantities the determinism contract quarantines are exactly the ones
    the perf-trajectory gate compares (with a relative threshold, since
    they are machine-noisy by nature)."""
    out = {}
    if isinstance(obj, dict):
        for k in sorted(obj):
            p = f"{prefix}.{k}" if prefix else str(k)
            out.update(collect_timings(obj[k], under_timing or k == "timing",
                                       p))
    elif isinstance(obj, (list, tuple)):
        for i, v in enumerate(obj):
            out.update(collect_timings(v, under_timing, f"{prefix}[{i}]"))
    elif under_timing and isinstance(obj, (int, float)) \
            and not isinstance(obj, bool):
        out[prefix] = float(obj)
    return out


def diff_deterministic(base, cur, path: str = "", rtol: float = 1e-5,
                       atol: float = 1e-8) -> list:
    """Paths where two canonical (volatile-stripped) metric trees disagree.

    Floats compare with (rtol, atol) so a serialization round-trip never
    counts as drift; everything else must match exactly."""
    if isinstance(base, dict) and isinstance(cur, dict):
        msgs = []
        for k in sorted(set(base) | set(cur)):
            p = f"{path}.{k}" if path else str(k)
            if k not in cur:
                msgs.append(f"{p}: missing from current run")
            elif k not in base:
                msgs.append(f"{p}: not in baseline")
            else:
                msgs += diff_deterministic(base[k], cur[k], p, rtol, atol)
        return msgs
    if isinstance(base, (list, tuple)) and isinstance(cur, (list, tuple)):
        if len(base) != len(cur):
            return [f"{path}: length {len(base)} -> {len(cur)}"]
        return [m for i, (b, c) in enumerate(zip(base, cur))
                for m in diff_deterministic(b, c, f"{path}[{i}]", rtol, atol)]
    if isinstance(base, float) or isinstance(cur, float):
        try:
            if abs(float(base) - float(cur)) <= atol + rtol * abs(float(base)):
                return []
        except (TypeError, ValueError):
            pass
        return [f"{path}: {base!r} -> {cur!r}"]
    if base != cur:
        return [f"{path}: {base!r} -> {cur!r}"]
    return []


# timing leaves where *higher* is better (throughput-style): a drop past
# the threshold is the regression, a rise never is. Matched against the
# leaf key name (last path segment).
HIGHER_IS_BETTER_MARKERS = ("qps", "rate", "throughput", "per_sec")


def _higher_is_better(path: str) -> bool:
    leaf = path.rsplit(".", 1)[-1]
    return any(m in leaf for m in HIGHER_IS_BETTER_MARKERS)


def compare_records(name: str, baseline: dict, current: dict,
                    threshold: float = 5.0) -> list:
    """Failure messages for one bench record vs its committed baseline.

    Three failure classes: (1) the bench's effective argv changed — the
    baseline measures a different configuration, re-record it; (2)
    deterministic drift — any non-``timing`` metric disagrees; (3) timing
    regression — a ``timing`` leaf more than ``threshold`` (fractional)
    *worse* than baseline, where worse means slower for latency-style
    leaves and lower for throughput-style ones (``qps``/``rate``/
    ``throughput``/``per_sec`` in the leaf name). Timing leaves only in
    one of the two records are ignored (new measurements have no baseline
    yet); improvements never fail."""
    if baseline.get("argv") != current.get("argv"):
        return [f"{name}: argv changed {baseline.get('argv')} -> "
                f"{current.get('argv')}; re-record the baseline with "
                f"--update-baseline"]
    fails = [f"{name}: deterministic drift at {m}" for m in
             diff_deterministic(canonical_metrics(baseline.get("metrics", {})),
                                canonical_metrics(current.get("metrics", {})))]
    base_t = collect_timings(baseline.get("metrics", {}))
    cur_t = collect_timings(current.get("metrics", {}))
    for key in sorted(set(base_t) & set(cur_t)):
        b, c = base_t[key], cur_t[key]
        if b <= 0:
            continue
        if _higher_is_better(key):
            if c < b / (1.0 + threshold):
                fails.append(
                    f"{name}: timing regression at {key}: {c:.6g} vs "
                    f"baseline {b:.6g} (-{(1 - c / b) * 100:.1f}% "
                    f"throughput > {threshold * 100:g}% threshold)")
        elif c > b * (1.0 + threshold):
            fails.append(
                f"{name}: timing regression at {key}: {c:.6g} vs baseline "
                f"{b:.6g} (+{(c / b - 1) * 100:.1f}% > "
                f"{threshold * 100:g}% threshold)")
    return fails


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=_ROOT, timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def write_record(record: dict, out_dir: str) -> str:
    """Persist one BENCH_<name>.json artifact; returns its path."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{record['bench']}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=str)
    return path


def run_one(name: str, mod, smoke: bool,
            json_out: str | None = None) -> tuple:
    """Run one benchmark under a controlled argv.

    Returns ``(failures, record)`` — the failure count (0 for
    informational benches) and the BENCH artifact record: bench name,
    effective argv, return code, wall-clock seconds, git sha, and whatever
    the module left in its ``METRICS`` dict. ``json_out``: directory to
    persist the record as ``BENCH_<name>.json``."""
    argv = [f"benchmarks/{name}.py"]
    if smoke:
        argv += list(getattr(mod, "SMOKE_ARGV", []))
    saved = sys.argv
    # run every bench under telemetry so artifacts say where time went,
    # not just totals; the snapshot lands under the record-level ``info``
    # key, which --compare never inspects (it diffs argv + metrics only)
    try:
        from repro import telemetry
    except ImportError:                              # pragma: no cover
        telemetry = None
    tel_was_enabled = False
    if telemetry is not None:
        tel_was_enabled = telemetry.enabled()
        telemetry.reset()
        telemetry.enable()
    t0 = time.perf_counter()
    try:
        sys.argv = argv
        rc = int(mod.main() or 0)
    finally:
        sys.argv = saved
    seconds = time.perf_counter() - t0
    info = {}
    if telemetry is not None:
        info = {"telemetry": telemetry.snapshot()}
        telemetry.reset()
        if not tel_was_enabled:
            telemetry.disable()
    # round-trip through JSON so in-memory records and ones re-read from
    # disk (the baselines --compare loads) are structurally identical
    # (tuples -> lists, numpy scalars -> str/float)
    record = json.loads(json.dumps(
        dict(bench=name, argv=argv[1:], smoke=smoke, returncode=rc,
             seconds=round(seconds, 3), git_sha=_git_sha(),
             metrics=getattr(mod, "METRICS", {}), info=info), default=str))
    if json_out:
        print(f"(wrote {write_record(record, json_out)})")
    if rc and getattr(mod, "INFORMATIONAL", False):
        print(f"({name} is informational — not counted as a failure)")
        return 0, record
    return rc, record


def main(argv: list | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("modules", nargs="*",
                    help="subset of registered benchmarks (default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="run every bench's smoke path (the CI gate)")
    ap.add_argument("--list", action="store_true",
                    help="print the registry and exit")
    ap.add_argument("--json-out", metavar="DIR",
                    help="persist a BENCH_<name>.json artifact per bench "
                         "(name, argv, metrics, git sha) into DIR")
    ap.add_argument("--compare", action="store_true",
                    help="diff each bench against its committed baseline "
                         "(BENCH_<name>.json in --baseline-dir): fail on "
                         "deterministic drift or timing regression beyond "
                         "--compare-threshold")
    ap.add_argument("--compare-threshold", type=float, default=5.0,
                    metavar="FRAC",
                    help="allowed fractional timing regression before "
                         "--compare fails (default 5.0 == 6x worse: "
                         "interpret-mode CPU micro-timings jitter several-"
                         "fold run-to-run, so the default catches order-of-"
                         "magnitude pathologies; tighten on real hardware)")
    ap.add_argument("--baseline-dir", default=_ROOT, metavar="DIR",
                    help="where baseline BENCH_<name>.json artifacts live "
                         "(default: repo root — the per-commit convention)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline artifacts in --baseline-dir "
                         "from this run (commit the result)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    registry = discover(args.modules or None)
    if args.list:
        for name, mod in registry.items():
            extras = []
            if getattr(mod, "SMOKE_ARGV", None):
                extras.append(f"smoke: {' '.join(mod.SMOKE_ARGV)}")
            if getattr(mod, "INFORMATIONAL", False):
                extras.append("informational")
            print(f"{name:20s} {'(' + ', '.join(extras) + ')' if extras else ''}")
        return

    failures = 0
    compare_failures = []
    for name, mod in registry.items():
        print(f"\n===== {name}{' (smoke)' if args.smoke else ''} =====")
        rc, record = run_one(name, mod, args.smoke, json_out=args.json_out)
        failures += rc
        if args.update_baseline:
            print(f"(baseline updated: {write_record(record, args.baseline_dir)})")
        elif args.compare:
            base_path = os.path.join(args.baseline_dir,
                                     f"BENCH_{name}.json")
            if not os.path.exists(base_path):
                compare_failures.append(
                    f"{name}: no baseline at {base_path}; record one with "
                    f"--update-baseline (and commit it)")
                continue
            with open(base_path) as f:
                baseline = json.load(f)
            msgs = compare_records(name, baseline, record,
                                   threshold=args.compare_threshold)
            for m in msgs:
                print(f"COMPARE FAIL: {m}")
            if not msgs:
                print(f"(compare vs {base_path}: ok)")
            compare_failures += msgs
    if compare_failures:
        print(f"\n{len(compare_failures)} baseline comparison failure(s):")
        for m in compare_failures:
            print(f"  - {m}")
    if failures or compare_failures:
        sys.exit(f"{failures} benchmark validations and "
                 f"{len(compare_failures)} baseline comparisons failed")
    print(f"\nall {len(registry)} benchmark validations passed"
          + (" (baselines match)" if args.compare else ""))


if __name__ == "__main__":
    main()
