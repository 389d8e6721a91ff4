"""Roofline tables from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Emits benchmarks/results/roofline_<mesh>.md + a machine-readable JSON.
"""
from __future__ import annotations

import dataclasses
import json
import os

from repro.roofline import load_cells, markdown_table

RESULTS = os.path.join(os.path.dirname(__file__), "results")
DRYRUN = os.path.join(RESULTS, "dryrun")


BASELINE_DIR = os.path.join(RESULTS, "dryrun_baseline")


def run(log=print) -> dict:
    variants = []
    if os.path.isdir(BASELINE_DIR):
        variants.append(("baseline", BASELINE_DIR, "auto"))
    variants.append(("optimized", DRYRUN, "optimized"))
    cells = []
    for tag, path, variant in variants:
        sub = load_cells(path, variant=variant)
        if not sub and variant == "optimized":
            sub = load_cells(path, variant="auto")   # pre-optimized runs
        for mesh in ("16x16", "2x16x16"):
            md = markdown_table(sub, mesh)
            out = os.path.join(RESULTS, f"roofline_{mesh}_{tag}.md")
            with open(out, "w") as f:
                f.write(f"# Roofline — mesh {mesh} — {tag} presets\n\n"
                        + md)
        cells += sub
    if not cells:
        if log:
            log("no dry-run results found — run "
                "`python -m repro.launch.dryrun --all --both-meshes`")
        return {"cells": 0}
    with open(os.path.join(RESULTS, "roofline_cells.json"), "w") as f:
        json.dump([dataclasses.asdict(c) for c in cells], f, indent=1)
    ok = [c for c in cells if c.ok]
    train16 = [c for c in ok if c.mesh == "16x16"
               and c.shape == "train_4k" and c.variant == "optimized"]
    if log:
        log(f"{len(ok)}/{len(cells)} cell records ok; optimized "
            f"single-pod train_4k roofline fractions: " + ", ".join(
                f"{c.arch}={c.roofline_fraction * 100:.0f}%"
                for c in sorted(train16, key=lambda c: c.arch)))
    return {"cells": len(cells), "ok": len(ok)}


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    run()
