"""The benchmark's token <-> text mapping and chat template.

The program's hermetic ``ByteTokenizer`` decodes ids 0..255 as UTF-8 bytes
and every other id as nothing. Under seeded random weights that hides most
tokens from a streaming client: an unconstrained reply is almost all ids
above 255 (no content chunk at all), a constrained one is mostly bytes
above 0x7f (replacement characters, withheld and merged), and the decoded
text does not re-encode to the ids that were generated, so a re-sent
history misses the prefix trie where a real vocabulary's would hit.

This mapping keeps the byte tokenizer's ids, markers and byte values (the
constrained-decoding automaton sees the same bytes) and renders EVERY id as
exactly one character that encodes back to the same id, as the tokens of a
real vocabulary do. One content chunk is then one token, on the client's
own clock, and the served ids can be read back from the streamed text.

Nothing here imports JAX or the program: the load generator and the
reference use it as it is; ``server.py`` wraps it as the engine's tokenizer.
"""

from __future__ import annotations

# The byte tokenizer's marker ids (serving/tokenizer.py documents them; the
# comparison with the reference fails if the program's template differs).
PAD, BOS, EOS = 256, 257, 258
SYS, USER, ASSISTANT, END = 259, 260, 261, 262
_ROLE = {"system": SYS, "user": USER, "assistant": ASSISTANT, "tool": USER}

_SHIFT = 0x100            # ids >= 128 render above Latin-1
_SURROGATES = (0xD800, 0xE000)


def char_of(token_id: int) -> str:
    """The one character token ``token_id`` renders as."""
    if 0 <= token_id < 128:
        return chr(token_id)
    cp = token_id + _SHIFT
    if cp >= _SURROGATES[0]:
        cp += _SURROGATES[1] - _SURROGATES[0]
    return chr(cp)


def id_of(ch: str) -> int:
    """Inverse of ``char_of``."""
    cp = ord(ch)
    if cp < 128:
        return cp
    if cp >= _SURROGATES[1]:
        cp -= _SURROGATES[1] - _SURROGATES[0]
    token_id = cp - _SHIFT
    if token_id < 128:
        raise ValueError(f"character U+{ord(ch):04X} is no token")
    return token_id


def encode(text: str) -> list[int]:
    return [id_of(c) for c in text]


def decode(ids) -> str:
    return "".join(char_of(int(i)) for i in ids)


def template_ids(messages: list[dict]) -> list[int]:
    """Prompt ids of an OpenAI-style message list: BOS, then for each
    message its role marker, its content and END, then the ASSISTANT
    marker that opens the reply."""
    ids = [BOS]
    for m in messages:
        ids.append(_ROLE.get(m.get("role", "user"), USER))
        ids.extend(encode(m.get("content") or ""))
        ids.append(END)
    ids.append(ASSISTANT)
    return ids
