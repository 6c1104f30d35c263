"""The trace reduction on a small RECORDED trace (the first 400 device ops
of a chat-sat run on a v5e chip, PR 23, with their metadata as the
profiler wrote it; names cut to 240 characters) and on a hand-written one
that has what the recording lacks: two chips, a collective, an idle gap."""
from pathlib import Path

import pytest

from benchmark import xplane

FIXTURE = Path(__file__).parent / "fixtures" / "trace_v5e_chat_sat.txt"


@pytest.fixture(scope="module")
def recorded():
    return xplane.Trace.from_text_proto(FIXTURE.read_text())


def test_metadata_is_read_from_the_wire_format(recorded):
    meta = recorded.meta["/device:TPU:0"]
    cats = {m.get("hlo_category") for m in meta.values()}
    assert {"custom-call", "loop fusion", "convolution fusion",
            "data formatting"} <= cats
    kernel = [m for name, m in meta.items()
              if name.startswith("%attention.paged_prefill")]
    assert kernel and all(m["hlo_category"] == "custom-call" for m in kernel)
    assert any("prefill.mlp" in m.get("tf_op", "") for m in meta.values())


def test_recorded_trace_reduces_to_programs_scopes_and_categories(recorded):
    r = xplane.reduce(recorded)
    assert len(r.devices) == 1 and len(r.devices[0].ops) == 400
    dev = r.devices[0]
    assert {p for _, _, p in dev.modules} == {"prefill_step"}
    assert {op.program for op in dev.ops} == {"prefill_step"}
    keys = {op.key for op in dev.ops}
    assert "prefill_step/attention.paged_prefill:custom-call" in keys
    assert "prefill_step/prefill.mlp:convolution_fusion" in keys
    assert "prefill_step/prefill.attention:convolution_fusion" in keys
    # The kernel's scope is the innermost one, not prefill.attention.
    assert r.self_ns(scope="attention.paged_prefill",
                     category="custom-call") > 0
    assert r.self_ns(scope="prefill.attention", category="custom-call") == 0
    # Ops nest properly on the line (the layer loop encloses its body), so
    # the self times add up to the busy time exactly.
    assert sum(op.self_ns for op in dev.ops) == pytest.approx(r.busy_ns())
    assert r.self_ns(program="prefill_step") == pytest.approx(r.busy_ns())
    loop = [op for op in dev.ops if op.category == "while"]
    assert loop and all(op.self_ns < op.end - op.start for op in loop)
    assert r.program_events("prefill_step") == 1
    assert 0.0 <= r.idle_share() < 0.01
    top = r.top_ops(3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    assert r.exposed_collective_ns() == 0


def plane(pid, name, lines, meta):
    """Text form of one XPlane. lines: {line: [(metadata id, start us,
    duration us)]}; meta: {id: (name, category or None, tf_op or None)}."""
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (line, events) in enumerate(lines.items(), 1):
        out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
        out += [f"events {{ metadata_id: {m} offset_ps: {int(s * 1e6)} "
                f"duration_ps: {int(d * 1e6)} }}" for m, s, d in events]
        out.append("}")
    for mid, (ev, cat, tf_op) in meta.items():
        stats = ""
        if cat:
            stats += f' stats {{ metadata_id: 1 str_value: "{cat}" }}'
        if tf_op:
            stats += f' stats {{ metadata_id: 2 str_value: "{tf_op}" }}'
        out.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{ev}"{stats} }} }}')
    out.append('stat_metadata { key: 1 value { id: 1 name: "hlo_category" } }')
    out.append('stat_metadata { key: 2 value { id: 2 name: "tf_op" } }')
    out.append("}")
    return "\n".join(out)


META = {1: ("jit_decode_scan(77)", None, None),
        2: ("%while.3 = (...) while(...)", "while", None),
        3: ("%fusion.9 = bf16[8,14336] fusion(...)", "convolution fusion",
            "jit(decode_scan)/while/body/decode.mlp/dot_general"),
        4: ("%all-reduce.5 = bf16[8,4096] all-reduce(...)", "all-reduce",
            "jit(decode_scan)/while/body/decode.mlp/psum"),
        5: ("%attention.paged_decode.8 = bf16[8,2,4,128] custom-call(...)",
            "custom-call", "jit(decode_scan)/while/body/decode.attention/"
            "attention.paged_decode/pallas_call")}


def two_chips():
    """Per chip (microseconds): a module 0-100 whose layer loop 0-100 holds
    an MLP matmul 0-40, an all-reduce 40-60 and the decode kernel 60-90;
    then nothing until a second module 150-170 (kernel only). On chip 1
    the all-reduce is 45-60. The host ran 'decode' over the first gap."""
    ops0 = [(2, 0, 100), (3, 0, 40), (4, 40, 20), (5, 60, 30), (5, 150, 20)]
    ops1 = [(2, 0, 100), (3, 0, 45), (4, 45, 15), (5, 60, 30), (5, 150, 20)]
    mods = [(1, 0, 100), (1, 150, 20)]
    host = plane(3, "/host:CPU", {
        "python": [(1, 95, 60), (2, 120, 10)],
        "tf_XLATfrtTpuClient/1": [(3, 0, 200)]},
        {1: ("decode", None, None), 2: ("np.asarray(jax.Array)", None, None),
         3: ("ThreadpoolListener::Record", None, None)})
    return xplane.Trace.from_text_proto("\n".join([
        plane(1, "/device:TPU:0", {"XLA Modules": mods, "XLA Ops": ops0},
              META),
        plane(2, "/device:TPU:1", {"XLA Modules": mods, "XLA Ops": ops1},
              META), host]))


def test_busy_idle_collectives_and_gaps_on_two_chips():
    r = xplane.reduce(two_chips(), window_ns=200_000)
    assert len(r.devices) == 2
    # Busy: 0-100 (the loop covers its gaps) and 150-170 on both chips.
    assert r.busy_ns() == 120_000 and r.idle_share() == pytest.approx(0.4)
    # The loop's self time is what its body leaves uncovered: 90-100.
    assert r.self_ns(category="while") == 10_000
    assert r.self_ns(scope="decode.mlp", category="convolution_fusion") \
        == pytest.approx((40_000 + 45_000) / 2)
    assert r.self_ns(scope="attention.paged_decode") == 50_000
    assert r.self_ns(program="decode_scan") == 120_000
    assert r.program_events("decode_scan") == 2
    # Nothing else runs during the all-reduce: all of it is exposed.
    assert r.exposed_collective_ns() == pytest.approx((20_000 + 15_000) / 2)
    assert r.top_ops(2) == [
        ["decode_scan/attention.paged_decode:custom-call", 50_000 / 1e9],
        ["decode_scan/decode.mlp:convolution_fusion", 42_500 / 1e9]]
    # The 100-150 gap's middle (125) lies in both host spans; the
    # innermost names it. Runtime worker pools are not the program.
    assert r.idle_gaps() == [["host.np.asarray(jax.Array)", 50_000 / 1e9]]


def test_program_names():
    assert xplane.program_name("jit_prefill_step(18339941968962930558)") \
        == "prefill_step"
    assert xplane.program_name("jit__threefry_split(1)") == "_threefry_split"
