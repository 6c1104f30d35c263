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
from benchmark.reference.forward import RefConfig, logits
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
    ref, routed = logits(params, RefConfig.of(c), toks, last=48)
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
    ref, _ = logits(params, RefConfig.of(c), toks, last=40)
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
    exact, routed = logits(params, RefConfig.of(c), toks[:n + 8], last=n + 8)
    assert (routed.sum(-1) == 2).all()
    assert np.abs(served(n, n + 8) - exact).max() < 1e-4
    wide, _ = logits(params, RefConfig.of(c), toks[:128], last=128)
    assert np.abs(served(128, 128) - wide).max() > 0.1
