"""One general traffic generator. A traffic mix is a data file of
parameters and a ``trace_seed``; from them comes, the same in every run,
the ordered trace of ``(due time or None, prompt tokens, max_tokens,
session)``. ``--seed`` decides only what is IN the prompts (token ids),
never how long they are or when they arrive: replaying a recorded trace
with other words in it.

Lengths are stratified quantiles of the stated distribution in a
low-discrepancy order (a rotated Halton sequence: base 2 for prompt
lengths, 3 for ``max_tokens``, 5 for arrival gaps), so any prefix of the
trace offers the same work as any other prefix of its length.

Schema of a traffic file (every key but ``loop``, ``trace_seed``,
``prompt_tokens`` and ``max_tokens`` optional):

    loop            "closed" (``clients`` callers, each waits for its
                    reply) or "open" (a fixed schedule at ``rate_rps``)
    trace_seed      whole number; fixes lengths and arrivals
    clients         closed loop: callers
    stagger_s       closed loop: client k first sends at k * stagger_s
    rate_rps        open loop: mean arrivals a second, bursts included
    calibrated_for  open loop: the configuration whose knee set the rate
    lead_in_s       open loop: schedule played before the window opens
    burst           open loop: {"size": n, "every_s": t} — n requests at
                    one instant every t seconds, inside ``rate_rps``
    prompt_tokens   a length spec (below); chat template and BOS included
    max_tokens      a length spec
    sessions        {"count": n, "shared_prefix_tokens": p}: entry i
                    belongs to session i mod n, whose prompts all start
                    with the same p tokens
    temperature     sampling temperature (default 0: greedy)
    why, source, assumed, calibration
                    not parameters: why the mix exists, the public trace
                    or dataset its numbers come from (with the quantiles
                    taken from it), every number that is this
                    benchmark's own choice, and the sweep an open loop's
                    ``rate_rps`` is arithmetic on (``knee_rps``, the
                    commit swept, the rates, seconds a rate)

A length spec is ``{"kind": "cycle", "values": [...]}``,
``{"kind": "uniform", "min", "max"}`` or ``{"kind": "lognormal",
"median", "sigma", "min", "max"}``; the last two take ``"snap": m`` to
round to a multiple of m (which bounds the set of prefill buckets a cell
has to warm).
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path
from typing import Any

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Entry:
    index: int
    due_s: float | None       # open loop: seconds after traffic start
    prompt_tokens: int
    max_tokens: int
    session: int | None


@dataclasses.dataclass(frozen=True)
class Traffic:
    name: str
    loop: str
    trace_seed: int
    prompt_tokens: dict[str, Any]
    max_tokens: dict[str, Any]
    clients: int = 1
    stagger_s: float = 0.0
    rate_rps: float = 0.0
    calibrated_for: str = ""
    lead_in_s: float = 0.0
    burst: dict[str, Any] | None = None
    sessions: dict[str, Any] | None = None
    temperature: float = 0.0

    @classmethod
    def load(cls, path: Path) -> "Traffic":
        raw = json.loads(Path(path).read_text())
        for note in ("why", "source", "assumed",     # for the reader
                     "calibration"):
            raw.pop(note, None)
        known = {f.name for f in dataclasses.fields(cls)} - {"name"}
        unknown = set(raw) - known
        if unknown:
            raise ValueError(f"{path}: unknown traffic keys {sorted(unknown)}")
        t = cls(name=Path(path).stem, **raw)
        if t.loop not in ("closed", "open"):
            raise ValueError(f"{path}: loop must be 'closed' or 'open'")
        if t.loop == "closed" and t.clients < 1:
            raise ValueError(f"{path}: a closed loop needs clients >= 1")
        if t.loop == "open" and t.rate_rps <= 0:
            raise ValueError(f"{path}: an open loop needs rate_rps > 0")
        return t


def radical_inverse(i: int, base: int) -> float:
    """The i-th element (i >= 1) of the van der Corput sequence."""
    out, f = 0.0, 1.0 / base
    while i:
        out += f * (i % base)
        i //= base
        f /= base
    return out


def _rotation(trace_seed: int, dim: int) -> float:
    return float(np.random.default_rng([trace_seed, dim]).random())


def _unit(i: int, base: int, shift: float) -> float:
    """Rotated low-discrepancy point in (0, 1)."""
    u = (radical_inverse(i + 1, base) + shift) % 1.0
    return min(max(u, 1e-9), 1.0 - 1e-9)


def quantile(spec: dict[str, Any], u: float, i: int) -> int:
    """The length at quantile ``u`` of ``spec`` (entry ``i`` for cycles)."""
    kind = spec["kind"]
    if kind == "cycle":
        return int(spec["values"][i % len(spec["values"])])
    lo, hi = spec["min"], spec["max"]
    if kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "lognormal":
        x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown length kind {kind!r}")
    snap = spec.get("snap", 1)
    x = round(x / snap) * snap
    lo_s, hi_s = -(-lo // snap) * snap, hi // snap * snap
    return int(min(max(x, lo_s), hi_s))


def support(spec: dict[str, Any]) -> list[int]:
    """Every length ``spec`` can yield (what a cell has to warm)."""
    if spec["kind"] == "cycle":
        return sorted(set(int(v) for v in spec["values"]))
    snap = spec.get("snap", 1)
    lo, hi = -(-spec["min"] // snap) * snap, spec["max"] // snap * snap
    return list(range(lo, hi + 1, snap))


def make_trace(t: Traffic, n: int) -> list[Entry]:
    """The first ``n`` entries of the mix's trace."""
    shifts = [_rotation(t.trace_seed, d) for d in range(3)]
    n_sessions = (t.sessions or {}).get("count", 0)
    dues: list[float | None] = [None] * n
    if t.loop == "open":
        dues = _arrivals(t, n, shifts[2])
    out = []
    for i in range(n):
        out.append(Entry(
            index=i, due_s=dues[i],
            prompt_tokens=quantile(t.prompt_tokens, _unit(i, 2, shifts[0]), i),
            max_tokens=quantile(t.max_tokens, _unit(i, 3, shifts[1]), i),
            session=i % n_sessions if n_sessions else None))
    return out


def _arrivals(t: Traffic, n: int, shift: float) -> list[float]:
    """Due times: exponential gaps (stratified, so every stretch of the
    schedule carries its share) at the rate left after the bursts, merged
    with ``burst.size`` simultaneous requests every ``burst.every_s``."""
    size = (t.burst or {}).get("size", 0)
    every = (t.burst or {}).get("every_s", 0.0)
    burst_rate = size / every if size and every else 0.0
    base_rate = t.rate_rps - burst_rate
    if base_rate <= 0:
        raise ValueError(f"traffic {t.name}: bursts alone exceed rate_rps")
    times, now = [], 0.0
    for i in range(n):
        now += -math.log(1.0 - _unit(i, 5, shift)) / base_rate
        times.append(now)
    if burst_rate:
        horizon = times[-1]
        k = 1
        while k * every <= horizon:
            times.extend([k * every] * size)
            k += 1
    return sorted(times)[:n]


def prompt_ids(entry: Entry, seed: int, vocab: int, n_content: int,
               sessions: dict[str, Any] | None, reserved: int = 3
               ) -> np.ndarray:
    """``n_content`` token ids for ``entry`` under ``--seed``: uniform
    over the vocabulary above the ``reserved`` special ids. A session's
    shared prefix depends on the seed and the session alone."""
    rng = np.random.default_rng([seed, 1, entry.index])
    ids = rng.integers(reserved, vocab, size=n_content, dtype=np.int64)
    shared = (sessions or {}).get("shared_prefix_tokens", 0)
    if shared and entry.session is not None:
        k = min(shared, n_content)
        ids[:k] = np.random.default_rng([seed, 2, entry.session]).integers(
            reserved, vocab, size=shared, dtype=np.int64)[:k]
    return ids
