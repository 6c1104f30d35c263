"""A tokenizer for random weights: one character for every id of the
vocabulary, and back.

The program's ``ByteTokenizer`` decodes only ids below 256. Random weights
pick an id uniformly from the whole vocabulary, so under it 99% of the
generated tokens decode to nothing, no content frame is ever written, and
a client can time neither the first token nor the gaps. A served model's
tokenizer decodes every id to text; this one does the same with the least
machinery: id ``i`` is the character ``chr(i)`` where that is printable
ASCII or a newline, else ``chr(0x4000 + i)`` — or, from the id on that
this would put into the surrogate block (``0xD800``–``0xDFFF``, which
UTF-8 cannot carry), the character one block further, so vocabularies of
up to a million ids fit and every id below 38,912 maps as it always has.
One character is one token
in both directions, so the client counts the tokens of a frame by the
length of its text, and a prompt of an exact number of tokens is a string
of that many characters.
"""
from __future__ import annotations

from typing import Sequence

_SHIFT = 0x4000
_SURROGATES, _AFTER_SURROGATES = 0xD800, 0xE000
MAX_VOCAB = 1_000_000      # chr(0x4000 + 0x800 + 999_999) < chr(0x10FFFF)


def _plain(i: int) -> bool:
    return 32 <= i < 127 or i == 10


def _char(i: int) -> str:
    if _plain(i):
        return chr(i)
    o = _SHIFT + i
    return chr(o if o < _SURROGATES else o + _AFTER_SURROGATES - _SURROGATES)


class CharTokenizer:
    """Implements the program's ``TokenizerLike``. Id 1 begins a sequence,
    as in the Mistral vocabulary. NO id ends one: random weights emit id 2
    once in 32000 tokens, by the accident of a seed's prompts and not
    because an answer is over, and in a closed loop one answer cut short
    re-phases every request behind it (a long-document run with one such
    stop read ``ttft_p50_ms`` 3910 for 3248, PERF.md). Every answer is as
    long as its ``max_tokens``: the same work from every seed."""

    def __init__(self, vocab_size: int):
        if not 128 <= vocab_size <= MAX_VOCAB:
            raise ValueError(f"vocabulary of {vocab_size} ids does not fit")
        self.vocab_size = vocab_size
        self.bos_id: int | None = 1
        self.eos_ids: set[int] = set()
        self.pad_id = 0

    def encode(self, text: str) -> list[int]:
        out = []
        for ch in text:
            o = ord(ch)
            if o >= _AFTER_SURROGATES:
                o -= _AFTER_SURROGATES - _SURROGATES
            i = o - _SHIFT if o >= _SHIFT else o
            if not 0 <= i < self.vocab_size:
                raise ValueError(f"character {ch!r} is outside the vocabulary")
            out.append(i)
        return out

    def text_of(self, ids: Sequence[int]) -> str:
        return "".join(_char(i) for i in map(int, ids))

    def decode(self, ids: Sequence[int]) -> str:
        return self.text_of(i for i in ids if 0 <= i < self.vocab_size)

    def decode_bytes(self, ids: Sequence[int]) -> bytes:
        return self.decode(ids).encode("utf-8")

    def apply_chat_template(self, messages: list[dict],
                            add_generation_prompt: bool = True) -> str:
        # The ByteTokenizer's template, so prompts cost what they cost there.
        parts = [f"<|{m.get('role', 'user')}|>\n{m.get('content', '')}\n"
                 for m in messages]
        if add_generation_prompt:
            parts.append("<|assistant|>\n")
        return "".join(parts)

    def template_overhead(self) -> int:
        """Tokens a one-message prompt costs beyond its content."""
        bos = 1 if self.bos_id is not None else 0
        return bos + len(self.apply_chat_template(
            [{"role": "user", "content": ""}]))
