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
    r = xplane.reduce(two_chips())
    assert len(r.devices) == 2
    # Busy: 0-100 (the loop covers its gaps) and 150-170 on both chips, of
    # the 170 from the first to the last device event.
    assert r.busy_ns() == 120_000 and r.window_ns == 170_000
    assert r.idle_share() == pytest.approx(50 / 170)
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
    # The 100-150 gap's middle (125) lies in both host spans: the
    # program's names it first, the runtime's second. Runtime worker pools
    # are not the program. No markers: the span is first to last device
    # event, so there is no idle edge.
    assert r.idle_gaps() == [
        ["host.decode/np.asarray(jax.Array)", 50_000 / 1e9]]
    assert not r.marked and r.span == (0, 170_000)


HOST_META = {1: ("bench.trace_open", None, None),
             2: ("bench.trace_close", None, None),
             3: ("decode", None, None), 4: ("sched.flush", None, None),
             5: ("AllocateRawBuffer", None, None),
             6: ("PjitFunction(decode_scan)", None, None),
             7: ("ThreadpoolListener::Record", None, None)}


def marked(host_lines=None, ops=None, mods=None):
    """One chip, markers at 50 and 250 us (the span), written by a thread
    of their own. Device: a module -20..70 whose loop -20..70 holds a
    matmul -20..40 and the kernel 40..70 (straddles the opening); a
    kernel-only module 120..140 inside; a module 230..290 (loop, kernel
    230..260, matmul 260..290) that straddles the close; and a module
    300..320 wholly after it."""
    ops = ops or [(2, -20, 90), (3, -20, 60), (5, 40, 30), (5, 120, 20),
                  (2, 230, 60), (5, 230, 30), (3, 260, 30), (5, 300, 20)]
    mods = mods or [(1, -20, 90), (1, 120, 20), (1, 230, 60), (1, 300, 20)]
    shift = 1000                        # offsets in a text proto are unsigned
    host = plane(3, "/host:CPU", {
        "python3/marks": [(1, 50 + shift, 0.5), (2, 250 + shift, 0.5)],
        **{k: [(m, s + shift, d) for m, s, d in v]
           for k, v in (host_lines or {}).items()}}, HOST_META)
    dev = plane(1, "/device:TPU:0", {
        "XLA Modules": [(m, s + shift, d) for m, s, d in mods],
        "XLA Ops": [(m, s + shift, d) for m, s, d in ops]}, META)
    return xplane.Trace.from_text_proto(dev + "\n" + host), shift * 1000


def test_markers_bound_the_span_and_everything_is_clipped_to_it():
    trace, t0 = marked()
    r = xplane.reduce(trace)
    assert r.marked and r.span == (t0 + 50_000, t0 + 250_000)
    assert r.window_ns == 200_000
    dev = r.devices[0]
    # Clipped: 50-70 of the first module, 120-140, 230-250 of the third;
    # the fourth lies outside and is gone.
    assert [(s - t0, e - t0) for s, e, _ in dev.modules] == [
        (50_000, 70_000), (120_000, 140_000), (230_000, 250_000)]
    assert all(r.span[0] <= op.start <= op.end <= r.span[1] for op in dev.ops)
    assert r.busy_ns() == 60_000 and r.busy_ns() <= r.window_ns
    assert r.idle_share() == pytest.approx(0.7)
    # Self times are of what is left: the opening loop keeps nothing (its
    # kernel covers 50-70), the matmul that ended at 40 is gone, the
    # closing kernel keeps 230-250 and the matmul behind it nothing.
    assert r.self_ns(category="while") == 0
    assert r.self_ns(scope="attention.paged_decode") == 60_000
    assert r.self_ns(scope="decode.mlp") == 0
    assert sum(op.self_ns for op in dev.ops) == r.busy_ns()
    # A program execution counts as the part of it inside the span.
    assert r.program_events("decode_scan") == pytest.approx(
        20 / 90 + 1 + 20 / 60)
    assert r.top_ops(1) == [
        ["decode_scan/attention.paged_decode:custom-call", 60_000 / 1e9]]
    # Idle: 70-120 and 140-230 (no idle edge here: busy at both markers).
    assert sum(e - s for s, e in r.gaps()) == 140_000


def test_busy_cannot_exceed_the_window_whatever_the_trace_holds():
    """One op from before the opening to after the close: the stamped
    window of PR 24's refused run was shorter than what it divided."""
    trace, _ = marked(ops=[(5, 0, 400)], mods=[(1, 0, 400)])
    r = xplane.reduce(trace)
    assert r.busy_ns() == r.window_ns == 200_000
    assert r.idle_share() == 0.0 and r.idle_gaps() == []
    assert r.program_events("decode_scan") == pytest.approx(0.5)


def test_idle_edges_are_gaps_and_gaps_are_named_program_then_runtime():
    """Ops only at 100-110 and 180-190: the edges 50-100 and 190-250 are
    idle like the middle. Names: program span and runtime span, the
    program's span alone, the runtime's alone, neither; the benchmark's
    own markers and the worker pools never name a gap."""
    trace, t0 = marked(
        ops=[(5, 100, 10), (5, 180, 10)], mods=[(1, 100, 10), (1, 180, 10)],
        host_lines={
            "python3/engine": [(3, 40, 50), (4, 60, 20), (5, 70, 8),
                               (3, 130, 30)],
            "python3/loop": [(6, 200, 40), (5, 215, 10)],
            "tf_XLATfrtTpuClient/1": [(7, 0, 400)]})
    r = xplane.reduce(trace)
    assert [(s - t0, e - t0) for s, e in r.gaps()] == [
        (50_000, 100_000), (110_000, 180_000), (190_000, 250_000)]
    # 50-100, middle 75: decode 40-90 and sched.flush 60-80 cover it, the
    # inner program span wins; AllocateRawBuffer 70-78 is the runtime's.
    # 110-180, middle 145: decode 130-160 alone.
    # 190-250, middle 220: PjitFunction 200-240 and, inside it,
    # AllocateRawBuffer 215-225; no program span.
    assert r.idle_gaps() == [
        ["host.decode/other", 70_000 / 1e9],
        ["host.-/AllocateRawBuffer", 60_000 / 1e9],
        ["host.sched.flush/AllocateRawBuffer", 50_000 / 1e9]]
    assert sum(ns for _, ns in r.idle_gaps()) * 1e9 == pytest.approx(
        r.window_ns - r.busy_ns())
    # Neither kind of span over a gap.
    bare, _ = marked(ops=[(5, 100, 10)], mods=[(1, 100, 10)])
    assert xplane.reduce(bare).idle_gaps() == [
        ["host.-/other", 190_000 / 1e9]]
    # Host spans are cut to the span too.
    assert all(r.span[0] <= s < e <= r.span[1] for s, e, _ in r.host)


def test_a_trace_without_both_markers_gives_the_harness_no_device_facts(
        recorded):
    from benchmark import run
    r = xplane.reduce(recorded)
    assert not r.marked and r.devices
    assert r.window_ns == r.span[1] - r.span[0] > 0     # first to last event
    assert run.trace_facts(r) == ({}, {})
    facts, breakdown = run.trace_facts(xplane.reduce(marked()[0]))
    assert facts == {"busy_s": 60_000 / 1e9, "window_s": 200_000 / 1e9}
    assert set(breakdown["breakdown"]) == {"device_ops", "idle_gaps"}
    # The close marker before the open one is no span either.
    swapped = plane(3, "/host:CPU", {"python3": [(2, 10, 1), (1, 20, 1)]},
                    HOST_META)
    dev = plane(1, "/device:TPU:0", {"XLA Modules": [(1, 0, 30)],
                                     "XLA Ops": [(5, 0, 30)]}, META)
    r = xplane.reduce(xplane.Trace.from_text_proto(dev + "\n" + swapped))
    assert not r.marked and r.window_ns == 30_000


def test_describe_shows_where_the_markers_landed():
    trace, t0 = marked()
    lines = {(d["plane"], d["line"]): d for d in xplane.describe(trace, 2)}
    marks = lines[("/host:CPU", "python3/marks")]["marks"]
    assert [m["name"] for m in marks] == [xplane.MARK_OPEN,
                                          xplane.MARK_CLOSE]
    assert marks[0]["start_ns"] == t0 + 50_000
    assert lines[("/device:TPU:0", "XLA Ops")]["marks"] == []


def test_program_names():
    assert xplane.program_name("jit_prefill_step(18339941968962930558)") \
        == "prefill_step"
    assert xplane.program_name("jit__threefry_split(1)") == "_threefry_split"


def hybrid_trace(layers=8, paged=2, steps=3):
    """One chip, ``steps`` decode steps of a hybrid model (microseconds):
    each of ``layers`` layers runs an expert matmul 0-6 under a scope of
    the CONFIGURATION's (inside ``decode.mlp``); the first ``paged`` of
    them then call the paged decode kernel 6-8, the others a recurrent
    state update 6-9 under another of its scopes."""
    meta = {**META,
            6: ("%fusion.77 = bf16[8,1280] fusion(...)", "convolution fusion",
                "jit(decode_scan)/while/body/decode.mlp/decode.experts/dot"),
            7: ("%fusion.78 = f32[8,64,128,128] fusion(...)", "loop fusion",
                "jit(decode_scan)/while/body/decode.linear_state/mul")}
    ops, t = [], 0
    for _ in range(steps):
        for layer in range(layers):
            ops.append((6, t, 6))
            ops.append((5, t + 6, 2) if layer < paged else (7, t + 6, 3))
            t += 10
    mods = [(1, 0, t)]
    return xplane.Trace.from_text_proto(plane(
        1, "/device:TPU:0", {"XLA Modules": mods, "XLA Ops": ops}, meta))


def test_a_configurations_scopes_come_before_the_benchmarks():
    """Stop 6: a scope the program adds for a new layer kind fell under
    the scope around it (or ``-``) and ``scope_share`` of it read nothing.
    Handed to ``reduce``, innermost first, it files its ops."""
    from benchmark import spec
    trace = hybrid_trace()
    before = xplane.reduce(trace)
    assert before.self_ns(scope="decode.experts") == 0
    assert before.self_ns(scope="decode.mlp") == 24 * 6_000
    assert {op.scope for op in before.devices[0].ops} == {
        "decode.mlp", "attention.paged_decode", ""}
    scopes = spec.scopes({"scopes": ["decode.experts",
                                     "decode.linear_state"]})
    r = xplane.reduce(trace, scopes)
    assert r.self_ns(scope="decode.experts") == 24 * 6_000
    assert r.self_ns(scope="decode.mlp") == 0
    assert r.self_ns(scope="decode.linear_state") == 18 * 3_000
    assert r.self_ns(scope="attention.paged_decode") == 6 * 2_000
    assert "decode_scan/decode.linear_state:loop_fusion" in {
        op.key for op in r.devices[0].ops}
    # Absent: the tuple as it is, the reduction as it was.
    assert xplane.reduce(trace, spec.scopes({})).top_ops(5) == \
        before.top_ops(5)


@pytest.mark.parametrize("layer_kinds, steps_read", [
    ({"period": 4, "paged_attention": [0]}, 3.0),    # 2 of 8 layers paged
    ({}, 0.75),         # every layer taken to call the kernel: 4x wrong
])
def test_a_step_is_counted_by_the_layers_that_call_the_kernel(
        layer_kinds, steps_read):
    """Stop 5: ``step.decode_ms`` counts a step as one kernel event per
    PAGED layer, and ``kernel.paged_decode_roofline`` multiplies a call's
    least time by that many layers. With one layer in four paged, the
    model's depth read both four times wrong."""
    from benchmark import roofline, spec
    from benchmark.metrics import RequestLog
    from benchmark.reducers import REDUCERS, Measured
    config = {"layer_kinds": layer_kinds} if layer_kinds else {}
    shape = roofline.AttnShape(
        n_layers=spec.paged_attention_layers(config, 8), n_heads=8,
        n_kv_heads=1, head_dim=128, window=0, kv_bytes=2, kv_scale_bytes=0)
    log = RequestLog(index=0, rid="r", prompt_tokens=1000, max_tokens=4,
                     frames=[(0.5, 1), (1.5, 3)])
    m = Measured(logs=[log], t_open=0.0, t_close=2.0,
                 trace=xplane.reduce(hybrid_trace()), t_trace=(1.0, 2.0),
                 flight=[], counters_open={}, counters_close={}, slots=8,
                 shape=shape, peaks=roofline.PEAKS["TPU v5 lite"],
                 peak_hbm_bytes=None, config=config)
    total_ms = 3 * (8 * 6 + 2 * 2 + 6 * 3) / 1e3      # self time, no holes
    ms = REDUCERS["program_ms_per_step"](m, {
        "step_scope": "attention.paged_decode", "programs": ["decode_scan"]})
    assert ms == pytest.approx(total_ms / steps_read)
    # Three tokens after the first landed in the traced span, at contexts
    # 1000, 1001, 1002: one call each in every paged layer.
    least = sum(roofline.least_seconds(
        *roofline.paged_decode_cost([n], shape), m.peaks)[0]
        for n in (1000, 1001, 1002))
    share = REDUCERS["kernel_roofline"](m, {
        "kernel": "paged_decode", "scope": "attention.paged_decode"})
    assert share == pytest.approx(
        100 * least * shape.n_layers / (6 * 2e-6))
    assert m.config is config
