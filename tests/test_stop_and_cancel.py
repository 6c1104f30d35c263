"""Stop-string holdback across token boundaries + client-cancellation tests
(code-review findings on the engine)."""
import asyncio

import jax
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import Delta, GenRequest, InferenceEngine
from llmapigateway_tpu.engine.tokenizer import ByteTokenizer, IncrementalDetokenizer


@pytest.fixture(scope="module", params=("tiny-test", "tiny-mistral-test"))
def shared_engine(request, stop_engine):
    """Whole contexts, and the window's page ring (pages of 8: 9 a slot): a
    stop or a cancel must give back a ring's pages too."""
    cfg = LocalEngineConfig(preset=request.param, max_batch_size=2,
                            max_seq_len=128, prefill_chunk=32,
                            dtype="float32", decode_burst=4, kv_page_size=8)
    eng = InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])
    yield eng
    stop_engine(eng)


def _run_emission(engine, token_texts, stop, max_tokens=50):
    """Drive _emit_token directly with a scripted token stream."""
    tok = engine.tokenizer
    req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=max_tokens, stop=stop)
    req.detok = IncrementalDetokenizer(tok)
    req.slot = 0
    engine._running[0] = req
    engine._free_slots = [s for s in engine._free_slots if s != 0]
    deltas = []
    for text in token_texts:
        for b in text.encode():
            if req.done:
                break
            req.generated.append(b)
            engine._emit_token(req)
    if not req.done:
        engine._finish(req, "length")
    while not req.out_queue.empty():
        deltas.append(req.out_queue.get_nowait())
    return req, deltas


def test_stop_string_spanning_tokens(engine):
    """'END' arriving as 'EN' + 'D' must be fully excluded from the output."""
    req, deltas = _run_emission(engine, ["hello ", "EN", "D", "more"],
                                stop=["END"])
    text = "".join(d.text for d in deltas)
    assert text == "hello "
    assert req.finish_reason == "stop"
    assert "EN" not in text


def test_stop_prefix_that_never_completes_is_emitted(engine):
    """Held-back 'EN' must be released when the stop never completes."""
    req, deltas = _run_emission(engine, ["abc EN", "again"], stop=["END"])
    text = "".join(d.text for d in deltas)
    assert text == "abc ENagain"


def test_stop_string_within_single_token(engine):
    req, deltas = _run_emission(engine, ["one END two"], stop=["END"])
    assert "".join(d.text for d in deltas) == "one "
    assert req.finish_reason == "stop"


def test_multiple_stop_strings_earliest_wins(engine):
    req, deltas = _run_emission(engine, ["a B c D"], stop=["D", "B"])
    assert "".join(d.text for d in deltas) == "a "


async def test_cancelled_request_releases_slot(engine):
    """A cancelled request must stop generating and free its slot."""
    req = GenRequest(prompt_ids=engine.tokenizer.encode("hello"),
                     max_tokens=10_000)
    await engine.submit(req)
    # Wait for the first token, then cancel like a disconnecting client.
    delta = await asyncio.wait_for(req.out_queue.get(), timeout=30)
    req.cancelled = True
    for _ in range(200):
        if req.finish_reason is not None:
            break
        await asyncio.sleep(0.05)
    assert req.finish_reason == "cancelled"
    assert len(engine._free_slots) == engine.B
    # Engine still serves new work afterwards.
    req2 = GenRequest(prompt_ids=engine.tokenizer.encode("next"), max_tokens=3)
    await engine.submit(req2)
    async for _ in engine.stream(req2):
        pass
    assert req2.finish_reason in ("stop", "length")


def test_detokenizer_hf_sliding_window_is_bounded():
    """HF-path detokenizer must not re-decode the whole history per token."""
    class CountingTok:
        bos_id = None
        eos_ids = set()
        vocab_size = 1000
        def __init__(self):
            self.max_window = 0
        def decode(self, ids):
            self.max_window = max(self.max_window, len(ids))
            return "".join(chr(97 + (i % 26)) for i in ids)

    tok = CountingTok()
    detok = IncrementalDetokenizer(tok)
    out = "".join(detok.push(i) for i in range(500)) + detok.flush()
    assert len(out) == 500
    assert tok.max_window < 10      # window stays tiny regardless of length
