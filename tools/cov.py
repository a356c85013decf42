"""Stdlib line-coverage for the test suite.

The reference gates CI on line coverage of the compiled spec
(/root/reference/Makefile:49-58, pytest --cov=eth2spec.phase0.spec); this
image has neither coverage.py nor pytest-cov, so this module implements
the same capability on sys.monitoring (PEP 669, CPython 3.12+):

  * collection — `start(package_dir)` registers a LINE callback under the
    reserved COVERAGE_ID tool slot. The callback records (file, line) and
    returns sys.monitoring.DISABLE, which turns off that exact code
    location — every line traces at most once, so steady-state overhead
    on a 600-test suite is near zero (unlike sys.settrace).
  * denominator — executable lines are derived by compiling each package
    source and walking the code-object tree's co_lines() tables, the same
    ground truth the interpreter uses.
  * gating — run as a script, `--check` reads the JSON artifact a
    collection run wrote (tests/conftest.py triggers collection when
    CSTPU_COV=1) and exits 1 below `--floor`.

Usage:
  CSTPU_COV=1 python -m pytest tests/ -q     # writes out/coverage.json
  python tools/cov.py --check --floor 85     # gate (see Makefile citest-cov)
"""
import argparse
import json
import os
import sys
import types

_ARTIFACT = os.path.join("out", "coverage.json")
_executed: dict = {}     # abs filename -> set[int]
_package_dir = None


def _on_line(code, line):
    f = code.co_filename
    if f.startswith(_package_dir):
        s = _executed.get(f)
        if s is None:
            s = _executed[f] = set()
        s.add(line)
    return sys.monitoring.DISABLE


def start(package_dir: str, artifact: str = _ARTIFACT) -> None:
    """Begin collection over `package_dir`; write `artifact` at exit."""
    global _package_dir
    _package_dir = os.path.abspath(package_dir) + os.sep
    mon = sys.monitoring
    mon.use_tool_id(mon.COVERAGE_ID, "cstpu-cov")
    mon.register_callback(mon.COVERAGE_ID, mon.events.LINE, _on_line)
    mon.set_events(mon.COVERAGE_ID, mon.events.LINE)
    import atexit
    atexit.register(_dump, artifact)


def executable_lines(path: str) -> set:
    """Line numbers the compiler marks executable (co_lines ground truth)."""
    with open(path, encoding="utf-8") as f:
        src = f.read()
    lines: set = set()
    stack = [compile(src, path, "exec")]
    while stack:
        c = stack.pop()
        for _, _, ln in c.co_lines():
            if ln is not None:
                lines.add(ln)
        stack.extend(k for k in c.co_consts if isinstance(k, types.CodeType))
    # module docstrings/constant folding can report line 0/None artifacts
    lines.discard(0)
    return lines


def _dump(artifact: str) -> None:
    sys.monitoring.set_events(sys.monitoring.COVERAGE_ID, 0)
    per_file = {}
    tot_exec = tot_hit = 0
    for root, _, files in os.walk(_package_dir):
        for name in sorted(files):
            if not name.endswith(".py"):
                continue
            path = os.path.join(root, name)
            try:
                ex = executable_lines(path)
            except SyntaxError:
                continue
            hit = _executed.get(path, set()) & ex
            rel = os.path.relpath(path, os.path.dirname(_package_dir.rstrip(os.sep)))
            per_file[rel] = {"executable": len(ex), "hit": len(hit),
                             "pct": round(100 * len(hit) / len(ex), 1) if ex else 100.0}
            tot_exec += len(ex)
            tot_hit += len(hit)
    os.makedirs(os.path.dirname(artifact) or ".", exist_ok=True)
    pct = round(100 * tot_hit / tot_exec, 2) if tot_exec else 100.0
    with open(artifact, "w") as f:
        json.dump({"total_pct": pct, "hit": tot_hit, "executable": tot_exec,
                   "files": per_file}, f, indent=1, sort_keys=True)
    print(f"[cov] line coverage {pct}% ({tot_hit}/{tot_exec}) -> {artifact}",
          file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="gate on an existing artifact")
    ap.add_argument("--floor", type=float, default=80.0)
    ap.add_argument("--artifact", default=_ARTIFACT)
    args = ap.parse_args()
    if not args.check:
        ap.error("collection runs via CSTPU_COV=1 pytest; use --check here")
    with open(args.artifact) as f:
        data = json.load(f)
    worst = sorted(data["files"].items(), key=lambda kv: kv[1]["pct"])[:8]
    print(f"total: {data['total_pct']}% "
          f"({data['hit']}/{data['executable']} lines)")
    for rel, d in worst:
        print(f"  {d['pct']:5.1f}%  {rel}")
    if data["total_pct"] < args.floor:
        print(f"FAIL: coverage {data['total_pct']}% < floor {args.floor}%")
        return 1
    print(f"OK: coverage {data['total_pct']}% >= floor {args.floor}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
