"""Aggregate experiments/dryrun/*.json into the §Roofline table."""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from repro.configs import ARCH_IDS, SHAPES
from repro.util import enable_compile_cache


def load(dirpath: str = "experiments/dryrun") -> List[Dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(path) as f:
            rows.append(json.load(f))
    return rows


def fmt(rows: List[Dict], mesh: str = "16x16") -> str:
    out = ["| arch | shape | dom | compute_s | memory_s | coll_s | "
           "useful | MFU-bound | HBM GiB | cnt | status |",
           "|---|---|---|---|---|---|---|---|---|---|---|"]
    index = {(r["arch"], r["shape"]): r for r in rows
             if r.get("mesh") == mesh}
    for arch in ARCH_IDS:
        for shape in SHAPES:
            r = index.get((arch, shape))
            if r is None:
                out.append(f"| {arch} | {shape} | - | | | | | | | | missing |")
            elif r.get("status") == "n/a":
                out.append(f"| {arch} | {shape} | - | | | | | | | | "
                           f"N/A ({r['reason'][:40]}...) |")
            elif r.get("status") != "ok":
                out.append(f"| {arch} | {shape} | - | | | | | | | | FAIL |")
            else:
                rf = r["roofline"]
                ext = "L2x" if r.get("depth_extrapolated") else "1x"
                out.append(
                    f"| {arch} | {shape} | {rf['dominant'][:4]} "
                    f"| {rf['compute_s']:.3f} | {rf['memory_s']:.3f} "
                    f"| {rf['collective_s']:.3f} | {rf['useful_ratio']:.2f} "
                    f"| {rf['mfu_bound'] * 100:.1f}% "
                    f"| {r['memory']['temp_gib']:.1f} | {ext} | ok |")
    return "\n".join(out)


def main(dirpath: str = "experiments/dryrun") -> Dict:
    rows = load(dirpath)
    ok = [r for r in rows if r.get("status") == "ok"]
    na = [r for r in rows if r.get("status") == "n/a"]
    fail = [r for r in rows if r.get("status") == "fail"]
    print(f"roofline_table: {len(ok)} ok / {len(na)} n/a / "
          f"{len(fail)} fail / {len(rows)} total cells")
    if ok:
        print(fmt(rows))
    return {"ok": len(ok), "na": len(na), "fail": len(fail),
            "table_md": fmt(rows)}


if __name__ == "__main__":
    enable_compile_cache()
    main()
