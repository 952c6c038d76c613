"""Render EXPERIMENTS.md tables from results/dryrun.json."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict


def load(path: str = "results/dryrun.json") -> Dict:
    rows = json.loads(Path(path).read_text())
    return {tuple(r["key"]): r for r in rows}


def fmt_ms(s: float) -> str:
    return f"{s*1e3:,.1f}"


def dryrun_table(rows: Dict, mesh: str) -> str:
    out = ["| arch | shape | status | bytes/dev (GB) | compile (s) |",
           "|---|---|---|---:|---:|"]
    for key in sorted(rows):
        r = rows[key]
        if key[2] != mesh:
            continue
        if r["status"] == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | SKIP (documented) | — | — |")
            continue
        ms = r.get("memory_stats") or {}
        gb = (ms.get("argument_bytes", 0) + ms.get("temp_bytes", 0)) / 1e9
        out.append(f"| {r['arch']} | {r['shape']} | {r['status']} | "
                   f"{gb:.2f} | {r.get('compile_seconds', 0):.0f} |")
    return "\n".join(out)


def roofline_table(rows: Dict) -> str:
    out = ["| arch | shape | compute (ms) | memory (ms) | collective (ms) "
           "| dominant | useful-FLOPs | roofline frac |",
           "|---|---|---:|---:|---:|---|---:|---:|"]
    for key in sorted(rows):
        r = rows[key]
        if key[2] != "single" or r["status"] != "ok":
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {fmt_ms(r['compute_s'])} | "
            f"{fmt_ms(r['memory_s'])} | {fmt_ms(r['collective_s'])} | "
            f"{r['dominant']} | {r['useful_flops_ratio']:.3f} | "
            f"{r['roofline_fraction']:.4f} |")
    return "\n".join(out)

