"""Shared benchmark plumbing: every paper table/figure is a module with
``run() -> dict`` (printable rows + derived headline numbers)."""

from __future__ import annotations

import json
import os
import time


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6  # µs


def write_json(filename: str, payload: dict) -> str:
    """Persist a benchmark's result dict (e.g. ``BENCH_attention.json``) at
    the repo root so runs are diffable across PRs.  When a previous run exists,
    prints a per-row timing delta table (flagging >1.3× slowdowns) before
    overwriting.  Returns the path written."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, filename)
    prev = None
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
        except (OSError, ValueError):
            prev = None
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    if prev is not None:
        report = regression_report(prev, payload, name=filename)
        if report:
            print(report)
    return path


SLOWDOWN_FLAG_X = 1.3

_ID_FIELDS = ("net", "layer", "name", "case", "shape")


def _row_id(row: dict) -> tuple:
    return tuple(str(row[k]) for k in _ID_FIELDS if k in row)


def regression_report(prev: dict, new: dict, *, name: str = "",
                      threshold: float = SLOWDOWN_FLAG_X) -> str:
    """Per-row delta table between two benchmark payloads.

    Matches ``rows`` entries by their identity fields and compares every
    ``*_us`` timing column; ratios above ``threshold`` are flagged so a
    perf regression is visible right in the benchmark output.  Returns ""
    when there is nothing comparable."""
    prev_rows = {_row_id(r): r for r in prev.get("rows", [])
                 if isinstance(r, dict)}
    deltas, flagged = [], 0
    for row in new.get("rows", []):
        if not isinstance(row, dict):
            continue
        old = prev_rows.get(_row_id(row))
        if old is None:
            continue
        for col, val in row.items():
            if not col.endswith("_us") or not isinstance(val, (int, float)):
                continue
            was = old.get(col)
            if not isinstance(was, (int, float)) or was <= 0:
                continue
            ratio = val / was
            flag = f"SLOW>{threshold}x" if ratio > threshold else ""
            flagged += bool(flag)
            deltas.append({"row": ":".join(_row_id(row)) or "-", "col": col,
                           "prev_us": round(was, 1), "now_us": round(val, 1),
                           "ratio_x": round(ratio, 2), "flag": flag})
    if not deltas:
        return ""
    head = f"Δ vs previous {name or 'run'}".rstrip()
    tail = (f"{flagged} column(s) regressed more than {threshold}x"
            if flagged else "no timing regressions above threshold")
    return "\n".join([head, fmt_table(
        deltas, ["row", "col", "prev_us", "now_us", "ratio_x", "flag"]),
        tail])


def fmt_table(rows: list[dict], cols: list[str]) -> str:
    widths = {c: max(len(c), *(len(str(r.get(c, ""))) for r in rows))
              for c in cols}
    line = " | ".join(c.ljust(widths[c]) for c in cols)
    sep = "-+-".join("-" * widths[c] for c in cols)
    body = "\n".join(" | ".join(str(r.get(c, "")).ljust(widths[c])
                                for c in cols) for r in rows)
    return f"{line}\n{sep}\n{body}"
