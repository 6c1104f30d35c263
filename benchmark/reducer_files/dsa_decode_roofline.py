"""``dsa_decode_roofline``: what decode's attention over an indexer's
selection had to read in the traced span, over the device time it took
(ISSUE 51). The bytes are ``reference/keye_vl2.py`` ``dsa_decode_cost``'s:
every scored token's index key and every selected token's K and V rows, a
layer — the same whatever implements the read."""
from __future__ import annotations

from typing import Any

from .. import roofline
from ..reducers import Measured, reducer
from ..reference.keye_vl2 import dsa_decode_cost


def span_contexts(m: Measured) -> list[int]:
    """The context (its own key among them) of every decode step whose
    token arrived inside the traced span, from the client's frame log:
    every token after a request's first, as ``kernel_roofline`` counts the
    paged decode kernel's calls."""
    t0, t1 = m.t_trace
    out = []
    for r in m.logs:
        seen = 0
        for t, n in r.frames:
            for _ in range(n):
                if seen and t0 <= t < t1:
                    out.append(r.prompt_tokens + seen)
                seen += 1
    return out


@reducer
def dsa_decode_roofline(m: Measured, a: dict[str, Any]) -> float | None:
    """Least seconds for the span's decode steps' index keys and selected
    K/V rows over every served layer, as a percentage of the device time
    under ``scopes`` in ``programs``. ``None`` where the configuration has
    no indexer or the trace no such scope (a program without one)."""
    sa = m.config.get("sa_config")
    if m.trace is None or not m.trace.devices or not sa:
        return None
    ns = sum(m.trace.self_ns(program=p, scope=s)
             for p in a["programs"] for s in a["scopes"])
    if not ns:
        return None
    _, _, nbytes = dsa_decode_cost(
        span_contexts(m), sa["topk"], sa["indexer_head_dim"],
        m.shape.n_kv_heads, m.shape.head_dim)
    least = roofline.least_seconds(0.0, nbytes * m.shape.n_layers, m.peaks)[0]
    return 100.0 * least / (ns / 1e9)
