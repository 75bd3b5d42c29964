"""DES-vs-analytic validation of the collective-schedule advisor (the
paper's simulator applied to the TPU pod — DESIGN.md §3)."""
from __future__ import annotations

import json
from typing import Dict

from repro.roofline import V5E, advise_allreduce, analytic_time
from repro.util import enable_compile_cache


def main(quick: bool = False) -> Dict:
    rows = []
    meshes = [(2, 2), (4, 4)] if not quick else [(2, 2)]
    for mesh in meshes:
        n = mesh[0] * mesh[1]
        for mb in (1e6, 100e6):
            advs = advise_allreduce(mb, mesh)
            for a in advs:
                an = analytic_time(a.schedule, n, mb, V5E, mesh)
                err = abs(a.predicted_s - an) / an * 100
                rows.append({"mesh": f"{mesh[0]}x{mesh[1]}",
                             "bytes": mb, "schedule": a.schedule,
                             "des_s": a.predicted_s, "analytic_s": an,
                             "err_pct": err})
    print("advisor_validation (DES vs analytic ring formulas):")
    worst = 0.0
    for r in rows:
        worst = max(worst, r["err_pct"])
        print(f"  {r['mesh']} {r['bytes'] / 1e6:6.0f}MB "
              f"{r['schedule']:11s} des={r['des_s'] * 1e3:9.3f}ms "
              f"analytic={r['analytic_s'] * 1e3:9.3f}ms "
              f"err={r['err_pct']:.2f}%")
    print(f"  worst error: {worst:.2f}%")
    assert worst < 1.0, "DES disagrees with closed-form ring schedules"
    return {"rows": rows, "worst_err_pct": worst}


if __name__ == "__main__":
    enable_compile_cache()
    json.dump(main(), open("experiments/advisor_validation.json", "w"),
              indent=1)
