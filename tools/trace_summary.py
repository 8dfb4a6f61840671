#!/usr/bin/env python3
"""Summarize a jax.profiler trace by device time: per step, by the program's
named scopes, by the compiler's HLO category, by Pallas kernel.

The tools/timeline.py analog (ref: tools/timeline.py:131 converts the
reference's profiler proto to chrome tracing). jax writes an ``.xplane.pb``
under ``<trace_dir>/plugins/profile/<time>/``; ``chipbench/xplane.py`` reads
it with the stats of the events' metadata (``tf_op``: jax's name stack, the
``jax.named_scope``s among it; ``hlo_category``) and
``chipbench/scope_profile.py`` sums it, over the whole executions of the
program with most device time (the train step). This tool prints that
reduction; the chip benchmark's per-layer metrics read the same one.

Usage:
    python tools/trace_summary.py TRACE_DIR_OR_FILE [--top K] [--scopes A,B]

``--scopes`` adds names to the base vocabulary, as a chipbench configuration
file's ``"scopes"`` list does: ``rope,moe_router,moe_dispatch,moe_experts``
for ``models/olmoe.py``'s step.

where TRACE_DIR is what ``profiler.profiler(trace_dir=...)`` or
``jax.profiler.start_trace`` was given (the newest trace under it is read),
or the ``.xplane.pb[.gz]`` itself.
"""

import argparse
import os
import pathlib
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                       # CLI use from anywhere
    sys.path.insert(0, REPO)

from chipbench import scope_profile, xplane    # noqa: E402


def newest_trace(where):
    where = pathlib.Path(where)
    if where.is_file():
        return where
    files = sorted(where.rglob("*.xplane.pb*"), key=lambda f: f.stat().st_mtime)
    if not files:
        raise SystemExit(f"no .xplane.pb under {where}")
    return files[-1]


def summarize(where, top=15, scopes=()):
    """The lines to print for the newest trace under ``where``."""
    path = newest_trace(where)
    reduced = scope_profile.reduce_planes(xplane.load(path), scopes=scopes)
    if reduced is None:
        raise SystemExit(f"{path}: no device plane with a program on it (a "
                         f"CPU trace has none)")
    lines = [f"{path}"] + scope_profile.table(reduced)
    if not reduced["scoped_events"]:
        lines.append(scope_profile.NO_SCOPE)
    busy = reduced["busy_ns"]
    lines.append("== device time a step by HLO category ==")
    for name, ns in sorted(reduced["category_ns"].items(),
                           key=lambda kv: -kv[1])[:top]:
        lines.append(f"{name:40}{ns / 1e6:10.3f} ms{100 * ns / busy:8.2f}%")
    lines.append("== operations under no scope, by label ==")
    for name, ns in reduced["unscoped_ops"][:top]:
        lines.append(f"{name:40}{ns / 1e6:10.3f} ms{100 * ns / busy:8.2f}%")
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb[.gz]")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--scopes", default="",
                    help="comma-separated scopes beside the base vocabulary")
    a = ap.parse_args(argv)
    print("\n".join(summarize(a.trace, a.top,
                              [s for s in a.scopes.split(",") if s])))


if __name__ == "__main__":
    main(sys.argv[1:])
