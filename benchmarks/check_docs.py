"""Docs-consistency check: benchmark fields AND obs names must be documented.

Two contracts, one stdlib-only gate (CI runs it before any heavyweight
imports are warm):

  * ``docs/benchmarks.md`` is the contract for reading the benchmark
    trajectory files.  The check walks every ``BENCH_*.json`` at the
    repo root, collects EVERY dict key that occurs anywhere in the
    payload (top-level, ``env``, and per-record fields alike), and
    fails if any key is not mentioned — in backticks — in the doc.
    A file named in ``DOC_OVERRIDES`` is held against its plane's own
    doc instead.
  * ``docs/observability.md`` is the contract for the observability
    plane itself: every span / event / metric name the instrumentation
    can export (the catalog in ``src/repro/obs/names.py`` — imported
    here WITHOUT jax; ``repro.obs`` is stdlib-only by design) must
    appear, in backticks, in the doc.  Add an instrument without
    cataloging + documenting it and CI fails.

CI runs it right after the streaming smoke regenerates
``BENCH_stream.json``, so a new benchmark field cannot land without its
documentation:

    python benchmarks/check_docs.py
"""

from __future__ import annotations

import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOC = ROOT / "docs" / "benchmarks.md"
OBS_DOC = ROOT / "docs" / "observability.md"

SERVE_DOC = ROOT / "docs" / "serving.md"
OPTIMIZER_DOC = ROOT / "docs" / "optimizer.md"
TRAIN_DOC = ROOT / "docs" / "training.md"

#: bench files whose field contract lives in a doc other than
#: docs/benchmarks.md
DOC_OVERRIDES = {"BENCH_serve.json": SERVE_DOC,
                 "BENCH_optimizer.json": OPTIMIZER_DOC,
                 "BENCH_train.json": TRAIN_DOC}

#: serving-plane names (obs catalog entries prefixed ``serve.``, plus
#: the row-level query span) must ALSO appear in docs/serving.md — the
#: plane's own contract, on top of the observability-catalog check
SERVE_NAME_PREFIXES = ("serve.", "query.infer_rows")

#: cost-based-optimizer names must ALSO appear in docs/optimizer.md
OPTIMIZER_NAME_PREFIXES = ("optimizer.",)

#: streamed-training names must ALSO appear in docs/training.md
TRAIN_NAME_PREFIXES = ("train.",)


def collect_keys(payload) -> set[str]:
    """Every dict key anywhere in the (nested) JSON payload."""
    keys: set[str] = set()

    def walk(obj):
        if isinstance(obj, dict):
            for k, v in obj.items():
                keys.add(k)
                walk(v)
        elif isinstance(obj, list):
            for v in obj:
                walk(v)

    walk(payload)
    return keys


def _backticked(doc: pathlib.Path) -> set[str]:
    return set(re.findall(r"`([A-Za-z0-9_.:]+)`", doc.read_text()))


def check_bench_files() -> bool:
    docs = {DOC, *DOC_OVERRIDES.values()}
    missing_docs = [d for d in docs if not d.exists()]
    if missing_docs:
        for d in missing_docs:
            print(f"FAIL: {d.relative_to(ROOT)} does not exist")
        return True
    documented = {d: _backticked(d) for d in docs}
    bench_files = sorted(ROOT.glob("BENCH_*.json"))
    if not bench_files:
        print("FAIL: no BENCH_*.json files found to check")
        return True
    failed = False
    for path in bench_files:
        doc = DOC_OVERRIDES.get(path.name, DOC)
        keys = collect_keys(json.loads(path.read_text()))
        missing = sorted(keys - documented[doc])
        if missing:
            failed = True
            print(f"FAIL {path.name}: keys missing from "
                  f"{doc.relative_to(ROOT)}: {', '.join(missing)}")
        else:
            print(f"OK   {path.name}: all {len(keys)} keys documented "
                  f"({doc.relative_to(ROOT)})")
    return failed


def check_obs_names() -> bool:
    """Every name in the obs catalog must appear in docs/observability.md.

    ``repro.obs.names`` is stdlib-only (the repo uses a namespace
    package under src/), so the import needs no jax — just the path.
    """
    if not OBS_DOC.exists():
        print(f"FAIL: {OBS_DOC.relative_to(ROOT)} does not exist")
        return True
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.obs import names as obs_names
    finally:
        sys.path.pop(0)
    documented = _backticked(OBS_DOC)
    failed = False
    for label, catalog in (("span", obs_names.SPAN_NAMES),
                           ("span-prefix", obs_names.SPAN_PREFIXES),
                           ("event", obs_names.EVENT_NAMES),
                           ("metric", obs_names.METRIC_NAMES)):
        missing = sorted(n for n in catalog
                         if n.rstrip(":") not in documented
                         and n not in documented)
        if missing:
            failed = True
            print(f"FAIL obs {label} names missing from "
                  f"{OBS_DOC.relative_to(ROOT)}: {', '.join(missing)}")
        else:
            print(f"OK   obs {label} names: all {len(catalog)} documented")
    return failed


def check_serve_names() -> bool:
    """Serving-plane span/event/metric names must also be documented in
    ``docs/serving.md`` — the serve plane's own contract doc (the
    observability catalog check above covers docs/observability.md)."""
    if not SERVE_DOC.exists():
        print(f"FAIL: {SERVE_DOC.relative_to(ROOT)} does not exist")
        return True
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.obs import names as obs_names
    finally:
        sys.path.pop(0)
    documented = _backticked(SERVE_DOC)
    serve_names = sorted(
        n for catalog in (obs_names.SPAN_NAMES, obs_names.EVENT_NAMES,
                          obs_names.METRIC_NAMES)
        for n in catalog if n.startswith(SERVE_NAME_PREFIXES))
    missing = sorted(n for n in serve_names if n not in documented)
    if missing:
        print(f"FAIL serve-plane names missing from "
              f"{SERVE_DOC.relative_to(ROOT)}: {', '.join(missing)}")
        return True
    print(f"OK   serve-plane names: all {len(serve_names)} documented "
          f"({SERVE_DOC.relative_to(ROOT)})")
    return False


def check_optimizer_names() -> bool:
    """Optimizer span/event/metric names must also be documented in
    ``docs/optimizer.md`` — the decision plane's own contract doc."""
    if not OPTIMIZER_DOC.exists():
        print(f"FAIL: {OPTIMIZER_DOC.relative_to(ROOT)} does not exist")
        return True
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.obs import names as obs_names
    finally:
        sys.path.pop(0)
    documented = _backticked(OPTIMIZER_DOC)
    opt_names = sorted(
        n for catalog in (obs_names.SPAN_NAMES, obs_names.EVENT_NAMES,
                          obs_names.METRIC_NAMES)
        for n in catalog if n.startswith(OPTIMIZER_NAME_PREFIXES))
    missing = sorted(n for n in opt_names if n not in documented)
    if missing:
        print(f"FAIL optimizer names missing from "
              f"{OPTIMIZER_DOC.relative_to(ROOT)}: {', '.join(missing)}")
        return True
    print(f"OK   optimizer names: all {len(opt_names)} documented "
          f"({OPTIMIZER_DOC.relative_to(ROOT)})")
    return False


def check_train_names() -> bool:
    """Streamed-training span/metric names must also be documented in
    ``docs/training.md`` — the training plane's own contract doc."""
    if not TRAIN_DOC.exists():
        print(f"FAIL: {TRAIN_DOC.relative_to(ROOT)} does not exist")
        return True
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.obs import names as obs_names
    finally:
        sys.path.pop(0)
    documented = _backticked(TRAIN_DOC)
    train_names = sorted(
        n for catalog in (obs_names.SPAN_NAMES, obs_names.EVENT_NAMES,
                          obs_names.METRIC_NAMES)
        for n in catalog if n.startswith(TRAIN_NAME_PREFIXES))
    missing = sorted(n for n in train_names if n not in documented)
    if missing:
        print(f"FAIL training names missing from "
              f"{TRAIN_DOC.relative_to(ROOT)}: {', '.join(missing)}")
        return True
    print(f"OK   training names: all {len(train_names)} documented "
          f"({TRAIN_DOC.relative_to(ROOT)})")
    return False


def main() -> int:
    failed = check_bench_files()
    failed = check_obs_names() or failed
    failed = check_serve_names() or failed
    failed = check_optimizer_names() or failed
    failed = check_train_names() or failed
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
