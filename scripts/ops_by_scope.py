#!/usr/bin/env python3
"""Every device op of a traced benchmark run's stretch, by named scope and HLO
name, in milliseconds a traced step: the table to lay beside the parent's when a
cell's rate moved and the result line's ten largest ops do not say why, and
the list of what no scope names (PERF.md section 5 is written from it).

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds 30 --trace 1
    python3 scripts/ops_by_scope.py <checkout root> <traced steps> <out.json>

Reads the trace the run left under ``<root>/benchmark/.state/trace`` with the
benchmark's own readers (``benchmark/xplane.py`` for the ops of the stretch,
``reducers/scope_time_share.scopes_of`` for each op's ``tf_op``); chip only in
the sense that only a chip run leaves such a trace.
"""
import json
import re
import sys
from collections import defaultdict
from pathlib import Path

#: a ComputationGraph's node scope, ``<node>.<Type>`` or ``<node>.<Type>.loss``
NODE = re.compile(r"[^/()]*\.[A-Z][A-Za-z0-9]+(?:\.loss)?$")


def scope_of(tf_op, scopes):
    """The scopes among a name stack's components, joined (``mtp/flash_fwd``);
    else its node's type (``<node>.ConvolutionLayer``, ``loss``); else ``-``."""
    parts = [p for p in re.split(r"[/()]", tf_op) if p]
    named = list(dict.fromkeys(p for p in parts if p.startswith(scopes)))
    if named:
        return "/".join(named)
    for p in parts:
        if NODE.match(p):
            kind = p.rsplit(".", 1)[-1]
            return kind if kind == "loss" else "<node>." + kind
    return "-"


def main(root, steps, out):
    root = Path(root).resolve()
    sys.path[:0] = [str(root), str(root / "benchmark")]
    import xplane
    from reducers import scope_time_share as sts
    try:    # the tree's own table of scopes; PR 36's where it has none
        from deeplearning4j_tpu.zoo import transformer as tfm
        scopes = tuple(n for n, _ in tfm.STEP_SCOPES) + tfm.KERNEL_SCOPES
    except (ImportError, AttributeError):
        scopes = ("moe_", "flash_", "lm_head", "mtp", "mla_", "cca_")
    tdir = root / "benchmark" / ".state" / "trace"
    tr = xplane.load(tdir, 1)
    tf = sts.scopes_of(xplane.find_xplane(tdir))
    agg = defaultdict(lambda: [0.0, 0])
    for s, e, name in tr.ops_in_window():
        if xplane._is_container(name):
            continue
        t = tf.get(name, "")
        scope = scope_of(t, scopes)
        own = xplane.short_name(name)
        if scope == "-" and own.startswith(("ragged-dot", "flash_")):
            scope = "(by name) " + own.split(".")[0].split(" ")[0]
        key = (scope, own, "/".join(t.split("/")[-3:]))
        agg[key][0] += (min(e, tr.t1) - max(s, tr.t0)) / 1e6
        agg[key][1] += 1
    rows = sorted(([*k, ms / steps, n / steps] for k, (ms, n) in agg.items()),
                  key=lambda r: -r[3])
    by_scope = defaultdict(float)
    for r in rows:
        by_scope[r[0]] += r[3]
    bare = [r for r in rows if r[0] == "-"]
    with open(out, "w") as f:
        json.dump({"busy_ms_step": tr.busy_s * 1e3 / steps,
                   "by_scope": dict(by_scope), "rows": rows[:400],
                   "unscoped": bare[:200]}, f)
    print("busy ms/step", round(tr.busy_s * 1e3 / steps, 2))
    for k, v in sorted(by_scope.items(), key=lambda x: -x[1])[:40]:
        print("  scope %-40s %8.2f" % (k, v))
    for title, some in (("largest", rows[:30]), ("unscoped", bare[:40])):
        print(" ", title)
        for r in some:
            print("  %-28s %-55s %-60s %7.2f x%.1f"
                  % (r[0][:28], r[1][:55], r[2][:60], r[3], r[4]))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), sys.argv[3])
