"""Controls of a benchmark cell's ``correct``: what it has to refuse.

    python3 tools/correct_controls.py [--decide CONTROL] [--samples N] -- \
        --workload command-a-plus-rag --seed 7 --seconds 5 --trace 0

ONE run of ``benchmark.run`` (its arguments follow the ``--``) in which each
entry of the reference module's ``CONTROLS`` (a change of the reference's
sizes: a coarser arithmetic, a wrong layer) stands in the reference's place,
through ``correctness.served_against_reference`` ITSELF — the harness's
sample served anew, its comparison, the cell's limits — on ``--samples``
samples, drawn from the seeds ``--seed`` onwards — and, where the module
has ``controlled_checks(engine, config, change)`` (the checks of its
``kernel_checks`` that compare served tokens with its logits: what the
harness's sample is too short to reach), through those too: `correct` is
the conjunction, so a control is refused when either refuses it. A line
``{"phase": "control", ...}`` each gives the readings (``gap_max``,
``gap_p50``; ``checks``) beside the limits and ``ok``, which must be false;
the sound reference's readings on the same samples are the lines whose
``control`` is null. The module's
``READINGS`` — what the comparison cannot refuse, and why — are taken the
same way and refuse nothing. The sound reference on the run's own seed
decides its ``correct`` as in any run, unless ``--decide`` names the control
that does: that run's result line must then read ``"correct": false``.
Exit code 0 when every control was refused on every sample (and, with
``--decide``, ``correct`` came out false), else 1.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import types
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import correctness, run      # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--decide", default=None)
    ap.add_argument("--samples", type=int, default=1)
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    sound_compare = correctness.served_against_reference
    refused: dict[str, list[bool]] = {}

    async def with_controls(gateway, seed, prompt_tokens, how, reference,
                            config):
        controls = getattr(reference, "CONTROLS", {})
        every = {None: None, **controls, **getattr(reference, "READINGS", {})}
        if args.decide not in (None, *controls):
            raise SystemExit(f"--decide: one of {list(controls)}")
        decided = None
        for sample in range(args.samples):
            for name, change in every.items():
                module = reference if name is None else types.SimpleNamespace(
                    sizes=lambda m, f, change=change: change(
                        reference.sizes(m, f)),
                    logits=reference.logits)
                got = await sound_compare(gateway, seed + sample,
                                          prompt_tokens, how, module, config)
                if hasattr(reference, "controlled_checks"):
                    got["checks"] = await asyncio.to_thread(
                        reference.controlled_checks, gateway.engine, config,
                        change)
                    got["ok"] = got["ok"] and all(
                        c["ok"] for c in got["checks"])
                run.emit("control", control=name, seed=seed + sample,
                         must_refuse=name in controls,
                         **{k: v for k, v in got.items() if k != "logs"})
                if name in controls:
                    refused.setdefault(name, []).append(not got["ok"])
                if sample == 0 and name == args.decide:
                    decided = got
        return decided

    sound_run_cell, result = run.run_cell, {}

    async def run_cell(*a, **kw):
        result.update(await sound_run_cell(*a, **kw))
        return result

    correctness.served_against_reference = with_controls
    run.run_cell = run_cell
    code = run.main([a for a in args.rest if a != "--"])
    as_due = bool(refused) and all(map(all, refused.values())) and (
        args.decide is None or result.get("correct") is False)
    print(json.dumps({"phase": "controls", "refused": refused,
                      "decided_by": args.decide or "the sound reference",
                      "correct": result.get("correct"), "as_due": as_due}))
    return code or (0 if as_due else 1)


if __name__ == "__main__":
    sys.exit(main())
