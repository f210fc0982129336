"""The benchmark's tokenizer: one visible word per vocabulary id.

The program's byte tokenizer decodes only ids 0..255, so on random
weights over a 100k+ vocabulary the client would see no text and could
time no token. This writes a HuggingFace ``tokenizers`` directory whose
word-level model maps ``w<i>`` to id ``i`` for every id of the
configuration's vocabulary: every generated id reaches the client as a
visible word, a prompt of n words is exactly n tokens, and there is no
bos/eos, so ``max_tokens`` alone ends a request. The client turns words
back into ids with :func:`ids_of` (stdlib only; the parent never loads
``transformers``).
"""

import json
import os


def write_tokenizer_dir(path: str, vocab_size: int) -> str:
    """Write ``tokenizer.json`` + ``tokenizer_config.json`` under
    ``path`` (idempotent: rewritten only when the size differs)."""
    os.makedirs(path, exist_ok=True)
    marker = os.path.join(path, "vocab_size")
    tok = os.path.join(path, "tokenizer.json")
    if os.path.exists(marker) and os.path.exists(tok):
        with open(marker) as f:
            if f.read().strip() == str(vocab_size):
                return path
    vocab = {f"w{i}": i for i in range(vocab_size)}
    doc = {
        "version": "1.0",
        "truncation": None,
        "padding": None,
        "added_tokens": [],
        "normalizer": None,
        "pre_tokenizer": {"type": "WhitespaceSplit"},
        "post_processor": None,
        "decoder": None,
        "model": {"type": "WordLevel", "vocab": vocab, "unk_token": "w0"},
    }
    with open(tok, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast"}, f)
    with open(marker, "w") as f:
        f.write(str(vocab_size))
    return path


def text_of(ids) -> str:
    """Token ids → the prompt text that encodes back to exactly them."""
    return " ".join(f"w{i}" for i in ids)


def ids_of(text: str) -> list:
    """Streamed text → token ids; raises ValueError on anything that is
    not a ``w<i>`` word (a delta the tokenizer did not produce)."""
    out = []
    for word in text.split():
        if word[:1] != "w" or not word[1:].isdigit():
            raise ValueError(f"not a benchmark token: {word!r}")
        out.append(int(word[1:]))
    return out
