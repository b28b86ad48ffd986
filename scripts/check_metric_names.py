#!/usr/bin/env python
"""Lint the telemetry instrumentation sites (ISSUE 6 tooling).

Greps every ``.counter("...") / .gauge("...") / .histogram("...")`` call
in the instrumented trees and fails on:

- metric names outside the registered ``dl4j_`` namespace,
- counter names not ending in ``_total`` (Prometheus convention the
  registry also enforces at runtime),
- names with invalid characters,
- duplicate registrations: the same name used as two different
  instrument kinds anywhere in the tree (the runtime raises on the
  second registration — this catches it statically, before a rarely-
  exercised code path does),
- label cardinality (ISSUE 11): label NAMES must come from the small
  ``ALLOWED_LABELS`` allowlist (extend it deliberately, in review —
  every new label multiplies series), and nothing that smells like a
  request/trace/span id may appear as a label name or be fed as a
  label value (``replica=req.id`` style) — per-request identity
  belongs in spans and flight-recorder records, not the registry.

Also lints the DOCS (ISSUE 7): every ``dl4j_``-prefixed token in
docs/*.md + README.md must be a name some instrumentation site actually
registers (wildcards like ``dl4j_bench_*`` must match ≥1 registered
name; Prometheus exposition suffixes ``_bucket/_sum/_count`` resolve to
their histogram) — so a doc example can never promise a metric the
registry doesn't serve.

Wired into the test suite as a fast unit test (tests/test_obs.py), so a
stray name fails CI, not a Grafana query. Run standalone:
``python scripts/check_metric_names.py``.
"""

from __future__ import annotations

import re
import sys
from pathlib import Path
from typing import Dict, List, Set, Tuple

REPO = Path(__file__).resolve().parent.parent

# instrumented trees: the package + the bench/diag entry points.
# tests/ excluded on purpose — they register deliberately-bad names to
# assert the runtime rejects them.
SCAN = ["deeplearning4j_tpu", "bench.py", "scripts"]

# docs whose dl4j_ mentions must resolve to registered metric names
DOCS = ["docs", "README.md"]

# dl4j_-prefixed doc tokens that are NOT metrics (library/namespace
# mentions) — keep this list short and literal
DOC_NON_METRIC_TOKENS = {"dl4j_", "dl4j_*", "dl4j_tpu_native"}

_SITE = re.compile(
    r"\.(counter|gauge|histogram)\(\s*[\"']([^\"']+)[\"']")
_DOC_TOKEN = re.compile(r"dl4j_[a-zA-Z0-9_]*\*?")
_EXPO_SUFFIX = re.compile(r"_(bucket|sum|count)$")
_NAME_OK = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
NAMESPACE = "dl4j_"

# -------- label-cardinality lint (ISSUE 11) --------
# Every label NAME any instrumentation site registers. Extending this
# is a deliberate act: each new label multiplies time series, and an
# unbounded one (request id, trace id) melts the registry.
ALLOWED_LABELS = {"backend", "component", "config", "direction", "kernel",
                  "kind", "layer", "layout", "level", "mode", "phase",
                  "reason", "replica", "row", "stat", "unit", "verdict"}
# layout (ISSUE 38): dl4j_flash_layout_total's three kernel layouts
# per-prefix restriction (ISSUE 12/13): each observability plane may
# label ONLY from its own small fixed vocabulary — component names,
# stat kinds and probe-pair kinds are bounded sets, never per-request
# identity. A dl4j_mem_* gauge with a `reason` label (or a
# dl4j_fidelity_* gauge labeled by layer AND reason) is a design smell
# this catches before it ships.
PLANE_LABELS = {
    "dl4j_mem_": {"component", "replica"},
    "dl4j_kv_": {"component", "replica"},
    # phase (ISSUE 37): trace / lower / backend, JAX's three compile events
    "dl4j_compile_": {"component", "phase", "replica"},
    # numerics & fidelity plane (ISSUE 13): layer/kind/replica only
    "dl4j_num_": {"kind", "layer", "replica"},
    "dl4j_fidelity_": {"kind", "layer", "replica"},
    "dl4j_replica_": {"replica"},
    # autotune harness (ISSUE 17): cache level, kernel kind, promotion
    # verdict, invalidation reason — all small fixed enums; the shape
    # bucket and sha stay in the cost-record key, never in a label
    "dl4j_autotune_": {"kernel", "level", "reason", "verdict"},
    # perf trend plane (ISSUE 15): the ledger key (row, backend) plus
    # the verdict enum — bench row names are a small fixed set; never
    # a sha, host fingerprint or capture id (those live in the ledger
    # records themselves)
    "dl4j_trend_": {"backend", "row", "verdict"},
    # fleet fabric (ISSUE 18): routing reason and scale direction are
    # tiny fixed enums; replica ids (r0, r1, ...) stay out of fleet
    # metric labels — per-replica series already exist on the
    # dl4j_serving_*/dl4j_slo_* planes under {replica=}
    "dl4j_fleet_": {"direction", "reason"},
    # quantization & speculation plane (ISSUE 19): storage/draft mode,
    # kernel kind and promotion verdict — all tiny fixed enums; shape
    # buckets and shas live in the autotune cost-record keys
    "dl4j_quant_": {"kernel", "mode", "verdict"},
    "dl4j_spec_": {"kernel", "mode", "verdict"},
    # multi-workload request plane (ISSUE 20): the RequestKind value
    # is the ONLY label — five fixed kinds, never per-request identity
    "dl4j_workload_": {"kind"},
}
# label names that smell like per-request/per-trace identity — never
# allowed even if someone adds them to the allowlist above by mistake
_ID_LABEL = re.compile(
    r"(^|_)(id|ids|uuid|request|requests|trace|span|session)(_|$)")
_LABELNAMES = re.compile(
    r"labelnames\s*=\s*[\(\[]\s*([^\)\]]*?)\s*[,\s]*[\)\]]")
_LABEL_LIT = re.compile(r"[\"']([^\"']+)[\"']")
# observation calls whose kwargs are label values: .inc/.set/.observe
_OBS_CALL = re.compile(r"\.(inc|set|observe)\(")
# a label VALUE expression that smuggles a request/trace id into the
# registry, e.g. `replica=req.id` / `reason=trace_id`
_ID_VALUE = re.compile(
    r"\b[a-z_]+\s*=\s*(?:str\(|f[\"'])?[^,()]*"
    r"\b(?:req(?:uest)?\.id|request_id|trace_id|span_id|\.trace_id\(\))")


def _files() -> List[Path]:
    out: List[Path] = []
    for entry in SCAN:
        p = REPO / entry
        if p.is_file():
            out.append(p)
        else:
            out.extend(sorted(f for f in p.rglob("*.py")
                              if "__pycache__" not in f.parts))
    return out


def _call_text(text: str, open_idx: int) -> str:
    """The argument text of a call: from the ``(`` at ``open_idx`` to
    its matching close paren. String-naive — adequate because help
    strings at these sites keep their parens balanced; a truncated
    match only makes the label lint conservative."""
    depth = 0
    for i in range(open_idx, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return text[open_idx:i + 1]
    return text[open_idx:open_idx + 400]


def check(files=None) -> List[str]:
    """Returns a list of human-readable violations (empty = clean)."""
    errors: List[str] = []
    kinds: Dict[str, Set[str]] = {}
    sites: Dict[str, List[str]] = {}
    for f in files or _files():
        if f.name == "check_metric_names.py":
            continue
        text = f.read_text()
        for m in _SITE.finditer(text):
            kind, name = m.group(1), m.group(2)
            try:
                shown = f.relative_to(REPO)
            except ValueError:   # explicit file list outside the repo
                shown = f
            where = f"{shown}:{text[:m.start()].count(chr(10)) + 1}"
            kinds.setdefault(name, set()).add(kind)
            sites.setdefault(name, []).append(where)
            if not _NAME_OK.match(name):
                errors.append(f"{where}: invalid metric name {name!r}")
            if not name.startswith(NAMESPACE):
                errors.append(f"{where}: {name!r} outside the registered "
                              f"{NAMESPACE} namespace")
            if kind == "counter" and not name.endswith("_total"):
                errors.append(f"{where}: counter {name!r} must end in "
                              "'_total'")
            args = _call_text(text, text.find("(", m.start()))
            lm = _LABELNAMES.search(args)
            for lab in (_LABEL_LIT.findall(lm.group(1)) if lm else ()):
                if _ID_LABEL.search(lab):
                    errors.append(
                        f"{where}: label {lab!r} on {name!r} looks like "
                        "a request/trace id — per-request identity "
                        "belongs in spans / flight-recorder records, "
                        "not metric labels")
                elif lab not in ALLOWED_LABELS:
                    errors.append(
                        f"{where}: label {lab!r} on {name!r} not in the "
                        f"allowlist {sorted(ALLOWED_LABELS)} — extend "
                        "ALLOWED_LABELS deliberately if this is a real "
                        "low-cardinality label")
                else:
                    for prefix, allowed in PLANE_LABELS.items():
                        if name.startswith(prefix) and lab not in allowed:
                            errors.append(
                                f"{where}: label {lab!r} on {name!r} — "
                                f"the {prefix}* plane restricts labels "
                                f"to {sorted(allowed)}")
        # label VALUES: an id smuggled into .inc/.set/.observe kwargs
        for m in _OBS_CALL.finditer(text):
            args = _call_text(text, text.find("(", m.start()))
            v = _ID_VALUE.search(args)
            if v:
                where = f"{f.relative_to(REPO) if f.is_relative_to(REPO) else f}" \
                        f":{text[:m.start()].count(chr(10)) + 1}"
                errors.append(
                    f"{where}: {v.group(0).strip()!r} feeds a "
                    "request/trace id as a metric label value — "
                    "unbounded cardinality; put it in a span or "
                    "flight-recorder record instead")
    for name, ks in sorted(kinds.items()):
        if len(ks) > 1:
            errors.append(
                f"duplicate registration of {name!r} as {sorted(ks)} "
                f"at {', '.join(sites[name])}")
    if files is None:     # full-tree run: docs must match the registry
        errors.extend(check_docs(set(kinds)))
    return errors


def _doc_files() -> List[Path]:
    out: List[Path] = []
    for entry in DOCS:
        p = REPO / entry
        if p.is_file():
            out.append(p)
        elif p.is_dir():
            out.extend(sorted(p.glob("*.md")))
    return out


def check_docs(known: Set[str], doc_files=None) -> List[str]:
    """Every dl4j_ token a doc promises must resolve to a registered
    instrumentation-site name (wildcard prefix / exposition suffix
    aware). Returns human-readable violations."""
    errors: List[str] = []
    for f in doc_files or _doc_files():
        text = f.read_text()
        for m in _DOC_TOKEN.finditer(text):
            tok = m.group(0)
            if tok in DOC_NON_METRIC_TOKENS:
                continue
            where = f"{f.relative_to(REPO) if f.is_relative_to(REPO) else f}" \
                    f":{text[:m.start()].count(chr(10)) + 1}"
            if tok.endswith("*"):
                prefix = tok[:-1]
                if not any(n.startswith(prefix) for n in known):
                    errors.append(
                        f"{where}: doc wildcard {tok!r} matches no "
                        "registered metric")
                continue
            base = _EXPO_SUFFIX.sub("", tok)
            if tok not in known and base not in known:
                errors.append(
                    f"{where}: doc mentions unregistered metric {tok!r} "
                    "(no .counter/.gauge/.histogram site registers it)")
    return errors


def main() -> int:
    errors = check()
    for e in errors:
        print(e, file=sys.stderr)
    n_names = len({m.group(2) for f in _files()
                   if f.name != "check_metric_names.py"
                   for m in _SITE.finditer(f.read_text())})
    n_doc = sum(len(_DOC_TOKEN.findall(f.read_text()))
                for f in _doc_files())
    print(f"check_metric_names: {n_names} metric names scanned, "
          f"{n_doc} doc mention(s) checked, {len(errors)} violation(s)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
