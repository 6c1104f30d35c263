"""What decides ``correct``: the reference forward is the program's
mathematics, a served token is judged by the reference's logits, and a
response is well formed or says why not (repair A.3: an early stop is not
a fault)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correctness
from benchmark.metrics import RequestLog
from benchmark.reference.forward import (RefConfig, logits,
                                         logits_and_routing)
from benchmark.tokenizer import CharTokenizer


@pytest.mark.parametrize("preset", ["tiny-mistral-test", "tiny-moe-test"])
def test_reference_forward_is_the_programs_mathematics(preset):
    """Same float32 weights, no quantisation: the plain reference (sliding
    window 16 inside a 48-token sequence; exact top-2 routing) and the
    program's forward agree to float32 rounding."""
    from llmapigateway_tpu.models import PRESETS, forward_fn, init_fn, llama
    c = PRESETS[preset]
    params = init_fn(c)(c, jax.random.PRNGKey(0), jnp.float32)
    toks = np.random.default_rng(0).integers(0, c.vocab_size, 48)
    cache = llama.KVCache.create(c, 1, 64, jnp.float32)
    served, _ = forward_fn(c)(params, c, jnp.asarray(toks[None], jnp.int32),
                              jnp.zeros((1,), jnp.int32), cache)
    ref, routed = logits_and_routing(params, RefConfig.of(c), toks, last=48)
    assert np.abs(np.asarray(served[0]) - ref).max() < 1e-4
    assert routed.shape == (c.n_layers, 48, c.n_experts)
    if c.n_experts:
        assert (routed.sum(-1) == c.experts_per_token).all()


def test_reference_reads_the_engines_int8_weight_tree():
    from llmapigateway_tpu.models import PRESETS, forward_fn, init_fn, llama
    from llmapigateway_tpu.models.quant import quantize_tree
    c = PRESETS["tiny-moe-test"]
    params = quantize_tree(init_fn(c)(c, jax.random.PRNGKey(1), jnp.float32),
                           c, "int8")
    toks = np.random.default_rng(1).integers(0, c.vocab_size, 40)
    cache = llama.KVCache.create(c, 1, 64, jnp.float32)
    served, _ = forward_fn(c)(params, c, jnp.asarray(toks[None], jnp.int32),
                              jnp.zeros((1,), jnp.int32), cache)
    ref = logits(params, RefConfig.of(c), toks, last=40)
    # W8A8 re-quantises activations the reference keeps in float32.
    assert 1e-4 < np.abs(np.asarray(served[0]) - ref).max() < 0.6


def test_the_programs_tokenizer_protocol_is_the_one_the_harness_implements():
    """The harness replaces ``engine.tokenizer`` (a change to the system
    under test made from inside the yardstick, PERF.md section 4). This
    fails when the program's protocol moves, so that the replacement
    cannot silently stop standing where the program's tokenizer stood."""
    import inspect
    from llmapigateway_tpu.engine import tokenizer as prog
    proto = {n: inspect.signature(f) for n, f in
             inspect.getmembers(prog.TokenizerLike, inspect.isfunction)
             if not n.startswith("_")}
    assert set(proto) == {"encode", "decode", "decode_bytes",
                          "apply_chat_template"}
    for name, sig in proto.items():
        for impl in (prog.ByteTokenizer, CharTokenizer):
            have = inspect.signature(getattr(impl, name))
            assert list(have.parameters) == list(sig.parameters), (impl, name)
    annotations = inspect.get_annotations(prog.TokenizerLike)
    byte, char = prog.ByteTokenizer(32000), CharTokenizer(32000)
    for attr in annotations:
        assert hasattr(byte, attr) and hasattr(char, attr), attr
    # Same template, so a prompt costs the tokens it costs in the program.
    msgs = [{"role": "user", "content": "abc"}]
    assert char.apply_chat_template(msgs) == byte.apply_chat_template(msgs)


def ok_log(**kw):
    base = dict(index=0, rid="r0", prompt_tokens=40, max_tokens=8,
                t_send=1.0, frames=[(1.1, 1), (1.2, 7)], t_end=1.3,
                status=200, done=True, finish_reason="length",
                usage={"prompt_tokens": 40, "completion_tokens": 8})
    return RequestLog(**{**base, **kw})


def test_a_well_formed_response_has_no_problems_and_an_early_stop_is_one():
    assert correctness.response_problems(ok_log()) == []
    stopped = ok_log(frames=[(1.1, 1), (1.2, 3)], finish_reason="stop",
                     usage={"prompt_tokens": 40, "completion_tokens": 5})
    assert correctness.response_problems(stopped) == []
    assert correctness.response_problems(ok_log(cancelled=True,
                                                done=False)) == []


@pytest.mark.parametrize("change, says", [
    (dict(status=429, error="shed"), "HTTP 429"),
    (dict(done=False), "no [DONE]"),
    (dict(usage=None), "no usage frame"),
    (dict(frames=[(1.1, 1)]), "1 were streamed"),
    (dict(error='{"message": "boom"}'), "boom"),
    (dict(usage={"prompt_tokens": 39, "completion_tokens": 8}),
     "counted as 39"),
    (dict(finish_reason=None), "finish_reason None"),
])
def test_a_malformed_response_says_what_is_wrong(change, says):
    log = ok_log(**change)
    assert any(says in p for p in correctness.response_problems(log))
    assert log.failed or "streamed" in says or "counted" in says \
        or "finish" in says


def test_ids_past_the_surrogate_block_fit_and_the_rest_map_as_before():
    """Stop 9. ``0x4000 + i`` reaches the surrogates at id 38,912; from
    there on an id takes the character one block further, so a vocabulary
    of 196,608 (or a million) ids fits. Every id below maps as the parent's
    tokenizer mapped it: the digests are of the PARENT's ``text_of`` over
    all 32,000 ids and over all 38,912 it could hold."""
    import hashlib
    import json
    parents = {
        32000: "6ee8115aa26f243c3275b46dc03027acd73720"
               "35d16490b6456cf495b4c142ab",
        38912: "12b2fb35e95eaea13605c5074f71493606218c"
               "237a793108b84572b1a6bd4f25"}
    for vocab, digest in parents.items():
        tok = CharTokenizer(vocab)
        text = tok.text_of(range(vocab))
        assert hashlib.sha256(text.encode("utf-8")).hexdigest() == digest
        assert tok.encode(text) == list(range(vocab))
    big = CharTokenizer(196608)
    assert big.text_of(range(38912)) == CharTokenizer(38912).text_of(
        range(38912))
    ids = list(range(38900, 38930)) + list(range(0, 196608, 97)) + [196607]
    text = big.text_of(ids)
    assert len(text) == len(ids) == len(set(text)) and big.encode(text) == ids
    assert not any(0xD800 <= ord(ch) < 0xE000 for ch in text)
    assert big.text_of([38911, 38912]) == chr(0xD7FF) + chr(0xE000)
    # What a frame does to it: UTF-8 and JSON both ways, one char a token.
    assert text.encode("utf-8").decode("utf-8") == text
    assert json.loads(json.dumps({"content": text}))["content"] == text
    from llmapigateway_tpu.engine.tokenizer import IncrementalDetokenizer
    detok = IncrementalDetokenizer(big)
    assert [detok.push(i) for i in (38912, 65, 196607)] == [
        chr(0xE000), "A", big.text_of([196607])]
    million = CharTokenizer(1_000_000)
    assert million.encode(million.text_of([999_999])) == [999_999]
    assert ord(million.text_of([999_999])) < 0x110000
    with pytest.raises(ValueError, match="outside the vocabulary"):
        CharTokenizer(38912).encode(chr(0xE000))
    with pytest.raises(ValueError, match="does not fit"):
        CharTokenizer(1_000_001)


def test_tokenizer_is_one_character_per_id_both_ways():
    tok = CharTokenizer(32000)
    ids = list(range(3, 32000, 7)) + [10, 65, 127, 255, 256, 16384, 31999]
    text = tok.text_of(ids)
    assert len(text) == len(ids) and tok.encode(text) == ids
    assert tok.decode(ids) == text
    assert text.encode("utf-8").decode("utf-8") == text and "�" not in text
    prompt = tok.apply_chat_template([{"role": "user", "content": text}])
    assert len(tok.encode(prompt)) + 1 == len(ids) + tok.template_overhead()
    # The program's incremental detokenizer emits each token as it comes.
    from llmapigateway_tpu.engine.tokenizer import IncrementalDetokenizer
    detok = IncrementalDetokenizer(tok)
    assert [detok.push(i) for i in (5000, 65, 31999)] == [
        tok.text_of([5000]), "A", tok.text_of([31999])]
    with pytest.raises(ValueError):
        CharTokenizer(100)


def test_no_id_ends_an_answer_so_every_seed_does_the_same_work():
    """Random weights emit any id once in a vocabulary's worth of tokens;
    an id that ended the answer would cut a seed's request short and, in a
    closed loop, re-phase every request behind it."""
    tok = CharTokenizer(32000)
    assert tok.eos_ids == set() and isinstance(tok.eos_ids, set)
    assert tok.bos_id == 1 and tok.pad_id == 0


def test_exact_routing_is_the_programs_only_where_dispatch_drops_nothing():
    """Eight experts, top-2. The reference never drops a token. The
    program agrees with it to float32 rounding through a 64-token prefill
    call and the decode steps after it (the regime the correctness sample
    of an expert model uses, ``DISPATCH_EXACT_TOKENS``), and does NOT
    through a 128-token call, where capacity dispatch gives each expert
    room for 64 tokens and random routers overflow it."""
    import dataclasses
    from llmapigateway_tpu.models import PRESETS, forward_fn, init_fn, llama
    c = dataclasses.replace(PRESETS["tiny-moe-test"], n_experts=8)
    params = init_fn(c)(c, jax.random.PRNGKey(0), jnp.float32)
    toks = np.random.default_rng(0).integers(0, c.vocab_size, 160)
    fwd = forward_fn(c)

    def served(first_call: int, n: int) -> np.ndarray:
        cache = llama.KVCache.create(c, 1, 192, jnp.float32)
        out, cache = fwd(params, c, jnp.asarray(toks[None, :first_call],
                                                jnp.int32),
                         jnp.zeros((1,), jnp.int32), cache)
        rows = [np.asarray(out[0])]
        for pos in range(first_call, n):      # decode, one token a step
            out, cache = fwd(params, c, jnp.asarray(toks[None, pos:pos + 1],
                                                    jnp.int32),
                             jnp.full((1,), pos, jnp.int32), cache)
            rows.append(np.asarray(out[0]))
        return np.concatenate(rows)

    n = correctness.DISPATCH_EXACT_TOKENS
    exact, routed = logits_and_routing(params, RefConfig.of(c),
                                       toks[:n + 8], last=n + 8)
    assert (routed.sum(-1) == 2).all()
    assert np.abs(served(n, n + 8) - exact).max() < 1e-4
    wide = logits(params, RefConfig.of(c), toks[:128], last=128)
    assert np.abs(served(128, 128) - wide).max() > 0.1


def test_the_default_sample_is_whole_chunks_at_the_benchmarks_bounds():
    how = correctness.sampling("c", {})
    assert how == correctness.Sampling(None, correctness.LOGIT_GAP_TOL,
                                       correctness.LOGIT_GAP_P50_TOL)
    assert (how.gap_tol, how.gap_p50_tol) == (0.25, 0.05)


def test_a_file_states_its_exact_regime_and_tighter_bounds_only():
    """Stop 2: what decided the sample was ``n_experts``; now it is what
    the configuration's file says of its program, and a file can tighten
    the bounds (with its reason) and never loosen them."""
    why = "bfloat16 weights: no activation is re-quantised"
    how = correctness.sampling("c", {"n_experts": 8, "correctness": {
        "exact_up_to_tokens": 64,
        "logit_gap_tol": {"value": 0.1, "why": why},
        "logit_gap_p50_tol": {"value": 0.0, "why": why}}})
    assert how == correctness.Sampling(64, 0.1, 0.0)
    # Experts alone decide nothing: a program that routes exactly at every
    # length is sampled at whole chunks like any other.
    assert correctness.sampling(
        "c", {"num_local_experts": 320}).exact_up_to_tokens is None
    for block, says in [
            ({"logit_gap_tol": {"value": 0.3, "why": why}}, "looser"),
            ({"logit_gap_p50_tol": {"value": 0.06, "why": why}}, "looser"),
            ({"logit_gap_tol": {"value": -1, "why": why}}, "looser"),
            ({"logit_gap_tol": 0.1}, "a value with its why"),
            ({"logit_gap_tol": {"value": 0.1}}, "a value with its why"),
            ({"exact_up_to_tokens": 0}, "exact_up_to_tokens 0"),
            ({"tolerance": 0.5}, "unknown correctness keys")]:
        with pytest.raises(ValueError, match=says):
            correctness.sampling("c", {"correctness": block})


def test_a_reference_module_keeps_the_contract(tmp_path):
    """Stop 1: the reference is found by the name in the configuration's
    file, in the cell's data directory or the benchmark's own, and a file
    that lacks one of the contract's names is refused where it is loaded."""
    from benchmark import reference
    from benchmark.reference import forward
    assert reference.load({}, tmp_path) is forward
    assert reference.load({"reference": "forward"}, tmp_path) is forward
    assert reference.CONTRACT == ("sizes", "logits")
    c = forward.sizes(_presets()["tiny-mistral-test"], {})
    assert c == RefConfig.of(_presets()["tiny-mistral-test"])
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference/half.py").write_text(
        "def sizes(model_cfg, config):\n    return None\n")
    with pytest.raises(TypeError, match=r"lacks \['logits'\]"):
        reference.load({"reference": "half"}, tmp_path)
    with pytest.raises(FileNotFoundError, match="reference/nowhere.py"):
        reference.load({"reference": "nowhere"}, tmp_path)


def _presets():
    from llmapigateway_tpu.models import PRESETS
    return PRESETS


def test_the_second_reference_is_the_programs_mathematics_too():
    """The rehearsal's architecture (``fixtures/tiny_hybrid``) brings a
    reference written apart from ``forward.py``. Both agree with the
    program's forward to float32 rounding on the same float32 weights, and
    its kernel check passes on them and FAILS on an expert layer that
    computes something else."""
    import dataclasses
    import types
    from benchmark import spec
    from llmapigateway_tpu.models import forward_fn, init_fn, llama
    fixtures = __import__("pathlib").Path(__file__).parent / "fixtures"
    ref = spec.load_module(fixtures, "tiny_hybrid", "reference")
    c = dataclasses.replace(_presets()["tiny-moe-test"], n_layers=4,
                            vocab_size=256)
    params = init_fn(c)(c, jax.random.PRNGKey(2), jnp.float32)
    toks = np.random.default_rng(2).integers(0, c.vocab_size, 40)
    cache = llama.KVCache.create(c, 1, 64, jnp.float32)
    served, _ = forward_fn(c)(params, c, jnp.asarray(toks[None], jnp.int32),
                              jnp.zeros((1,), jnp.int32), cache)
    file = {"num_experts_per_tok": 2}
    rows = ref.logits(params, ref.sizes(c, file), toks, last=40)
    assert rows.dtype == np.float32 and rows.shape == (40, 256)
    assert np.abs(np.asarray(served[0]) - rows).max() < 1e-4
    assert np.abs(logits(params, RefConfig.of(c), toks, 40) - rows).max() \
        < 1e-4
    engine = types.SimpleNamespace(model_cfg=c, params=params)
    [case] = ref.kernel_checks(engine, file, interpret=True)
    assert case["kernel"] == "moe_mlp_dense" and case["ok"]
    assert case["max_abs_err"] < 1e-4
    # One expert per token where the publication routes to two: not ok.
    [wrong] = ref.kernel_checks(engine, {"num_experts_per_tok": 1}, True)
    assert not wrong["ok"] and wrong["max_abs_err"] > 1e-2
