"""``cross_decode_roofline``: what a cross decoder's decode steps had to
read of the ONE full-context K/V in the traced span — every key once a
READING layer (the full layer and the cross layers) — over the device time
their attention took (ISSUE 54). The bytes are ``reference/phi4_flash.py``
``cross_decode_cost``'s: the same whatever implements the read."""
from __future__ import annotations

from typing import Any

from .. import roofline
from ..reducers import Measured, reducer
from ..reference.phi4_flash import cross_decode_cost


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


# What an op of the burst already in flight when the trace opened is filed
# under: the profiler's module event for a program whose launch it did not
# see (``_unknown`` in the breakdown's keys), or ``xplane``'s own name for an
# op whose start no module event holds.
UNFILED = ("_unknown", "unknown")


@reducer
def cross_decode_roofline(m: Measured, a: dict[str, Any]) -> float | None:
    """Least seconds for the span's decode steps' reads of the shared K/V
    as a percentage of the device time under ``scope`` in the decode
    ``programs`` — and in the burst in flight when the trace opens, whose
    ops lie under none of them (``UNFILED``: 0.10-0.13 s of 1.8 here) while
    its tokens' frames, which arrive inside the span, are in the numerator:
    the two sides hold the same steps. ``prefill_step``'s own time under the scope is in neither.
    ``None`` where the configuration is no cross decoder (its file states
    no ``mb_per_layer``) or the trace has no such scope (a program without
    one)."""
    if (m.trace is None or not m.trace.devices
            or "mb_per_layer" not in m.config):
        return None
    ns = sum(m.trace.self_ns(program=p, scope=a["scope"])
             for p in (*a["programs"], *UNFILED))
    if not ns:
        return None
    readers = m.config["num_hidden_layers"] // 4    # the full layer + cross
    _, nbytes = cross_decode_cost(
        span_contexts(m), readers, m.shape.n_kv_heads, m.shape.head_dim)
    return 100.0 * roofline.least_seconds(0.0, nbytes, m.peaks)[0] / (ns / 1e9)
