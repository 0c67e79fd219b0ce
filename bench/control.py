"""Read a cell's control, and the program beside it, on several seeds.

    python bench/control.py --workload <cell> --seeds 11,22,33 --seconds 8

Not part of a benchmark run: it gives the readings the limits in
``bench/limits/`` are set from (see PERF.md).  Each seed is one run of
the cell at its own size and load (a short window, long enough to
finish the longest requests), all in this one process, and prints one
JSON line with the numbers compared.

* scheduling cells: the control is the plain reference's EASY over
  socket slots, which lets two serial jobs share a node
  (``reference.easy.SharedNodes``), put in the program's place; its
  starts and allocations are checked as the program's are.
* serving cells: the program's widest served-token gap, and the
  control's: at each checked position the token an fp8 reference puts
  first, read against the float32 reference.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import run
    from harness.cell import load_cell
    cell = load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        if cell.traffic["driver"] == "sched":
            from harness.sched import ControlSite
            kw = {"system": ControlSite(cell.config["site"])}
        else:
            kw = {"control": True}
        extra = {}
        res = run.run_cell(args.workload, seed, args.seconds, False,
                           t_process=time.perf_counter(), cell=cell,
                           driver_kwargs=kw, on_extra=extra.update)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "control_gap": extra.get("control_gap"),
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
