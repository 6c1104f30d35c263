"""The knee of an open-loop cell, found once when the cell is defined:

    python3 -m benchmark.sweep --workload mistral-7b-chat \\
        --rates 0.8,1.2,1.6,2.0,2.4,2.8,3.2 --step-seconds 30

One process, one set-up; the cell's own traffic file is replayed at each
rate in turn (same lengths, same ``trace_seed``, gaps scaled), the engine
left to run empty in between. The knee is the highest rate whose backlog
— requests due and not yet admitted to a slot — is no larger at the end of
its step than a third of the way in. The cell then runs at four fifths of
it; the number goes into the traffic file by hand, with
``calibrated_for``. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
from pathlib import Path

from . import metrics, spec
from .run import (OUT_DIR, device_facts, emit, enable_compile_cache,
                  has_chips, reachable_programs, warm_programs)


def backlog(logs, t: float) -> int:
    return sum(1 for r in logs if r.t_due is not None and r.t_due <= t
               and (r.t_admitted is None or r.t_admitted > t))


async def sweep(cell: spec.Cell, rates: list[float], step_s: float,
                seed: int, out: Path) -> list[dict]:
    from .gateway import Gateway, resolve_preset
    from .load import Player
    preset = resolve_preset(cell.config_name, cell.config)
    rows = []
    async with Gateway({**cell.config["engine"], "preset": preset},
                       out / "gateway") as g:
        await asyncio.to_thread(warm_programs, g.engine,
                                reachable_programs(cell, g.engine))
        for rate in rates:
            traffic = dataclasses.replace(cell.traffic, rate_rps=rate,
                                          lead_in_s=0.0)
            played = await Player(g, traffic, seed, step_s).play()
            logs, t0, t1 = played.logs, played.t_open, played.t_close
            values, counts = metrics.end_to_end(logs, t0, t1)
            row = {"rate_rps": rate,
                   "backlog_third": backlog(logs, t0 + step_s / 3),
                   "backlog_end": backlog(logs, t1),
                   "sent": sum(1 for r in logs if r.t_send < t1),
                   "failed": sum(1 for r in logs if r.failed),
                   **{k: round(v, 3) for k, v in values.items()},
                   "samples": counts.get("ttft_p50_ms", 0)}
            row["sustained"] = (row["backlog_end"] <= row["backlog_third"]
                                and not row["failed"])
            emit("sweep", **row)
            rows.append(row)
            while True:                      # let the engine run empty
                st = g.engine.stats()
                if not st["running"] and not st["queued"]:
                    break
                await asyncio.sleep(0.2)
    return rows


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    device = device_facts()
    if not has_chips(cell, device):
        print(f"sweep: needs {cell.chips} TPU chip(s), JAX reports {device}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    out = spec.REPO_ROOT / OUT_DIR / f"{cell.name}.sweep"
    out.mkdir(parents=True, exist_ok=True)
    rows = asyncio.run(sweep(cell, [float(r) for r in args.rates.split(",")],
                             args.step_seconds, args.seed, out))
    sustained = [r["rate_rps"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_rps": max(sustained) if sustained else None,
                      "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
