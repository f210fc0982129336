"""Training data pipeline: tokenize, pack, shard, prefetch.

The input path for the fine-tune driver (reference ships data handling
only inside user examples; here it is part of the framework so
``dtpu apply`` of examples/llama-finetune-v5e.yaml is runnable as-is).

Three layers, each usable alone:

- **Sources** — ``load_tokens`` memory-maps a pre-tokenized corpus
  (``.npy`` [N, T] or flat ``.bin`` uint16/uint32), or tokenizes a
  ``.jsonl``/``.txt`` corpus with an HF tokenizer (zero-egress: the
  tokenizer must be a local path).
- **Packing** — ``pack_documents`` concatenates documents with an EOS
  separator and reshapes into fixed [N, seq_len+1] rows (the +1 yields
  next-token targets without wraparound), dropping the ragged tail:
  the standard LM packing that keeps every MXU step dense, no padding
  waste.
- **Iteration** — ``batches`` yields shuffled epoch batches
  {tokens, targets, mask} as host numpy; ``prefetch_to_device``
  double-buffers ``jax.device_put`` (with an optional NamedSharding for
  dp/fsdp-sharded batches) one step ahead, so the host→HBM copy of
  batch k+1 overlaps step k's compute.
"""

import json
from pathlib import Path
from typing import Iterator, Optional

import numpy as np

__all__ = ["load_tokens", "pack_documents", "batches", "prefetch_to_device"]


def _tokenize_texts(texts, tokenizer_path: str) -> list[np.ndarray]:
    from transformers import AutoTokenizer

    tok = AutoTokenizer.from_pretrained(tokenizer_path)
    eos = tok.eos_token_id
    docs = []
    for t in texts:
        ids = tok(t, add_special_tokens=False)["input_ids"]
        if eos is not None:
            ids = ids + [eos]
        docs.append(np.asarray(ids, np.int32))
    return docs


def load_tokens(
    path: str,
    seq_len: int,
    tokenizer: Optional[str] = None,
    bin_dtype: str = "uint16",
) -> np.ndarray:
    """Any supported corpus file → packed [N, seq_len+1] int32 rows.

    - ``.npy``: pre-tokenized; [N, T] rows are repacked when
      T != seq_len+1 (rows are assumed to carry their own separators —
      no token is injected between them), a flat [M] stream is
      reshaped directly.
    - ``.bin``: flat token stream (GPT-2 style); ``bin_dtype`` picks
      uint16/uint32 explicitly — guessing from content can silently
      fuse token pairs on pad-heavy uint16 corpora.
    - ``.jsonl``: one JSON object per line with a ``text`` field
      (requires ``tokenizer``; the separator is the TOKENIZER's eos,
      already appended by tokenization — never ``eos_id``).
    - ``.txt``: one document per line (requires ``tokenizer``).
    """
    p = Path(path)
    suffix = p.suffix.lower()
    if suffix == ".npy":
        arr = np.load(p, mmap_mode="r")
        if arr.ndim == 2 and arr.shape[1] == seq_len + 1:
            return np.asarray(arr, np.int32)
        if arr.ndim == 2:
            return pack_documents(
                list(np.asarray(arr, np.int32)), seq_len, eos_id=None
            )
        return _reshape_stream(np.asarray(arr, np.int32), seq_len)
    if suffix == ".bin":
        if bin_dtype not in ("uint16", "uint32"):
            raise ValueError(f"bin_dtype must be uint16/uint32, got {bin_dtype!r}")
        raw = np.fromfile(p, dtype=np.dtype(bin_dtype))
        return _reshape_stream(raw.astype(np.int32), seq_len)
    if suffix in (".jsonl", ".txt"):
        if tokenizer is None:
            raise ValueError(f"{suffix} corpus requires a tokenizer path")
        lines = p.read_text().splitlines()
        if suffix == ".jsonl":
            texts = [json.loads(ln)["text"] for ln in lines if ln.strip()]
        else:
            texts = [ln for ln in lines if ln.strip()]
        docs = _tokenize_texts(texts, tokenizer)
        # tokenization already appended the tokenizer's real EOS per
        # doc — insert no extra separators
        return pack_documents(docs, seq_len, eos_id=None)
    raise ValueError(f"unsupported corpus format {suffix!r} ({path})")


def _reshape_stream(stream: np.ndarray, seq_len: int) -> np.ndarray:
    """Flat pre-tokenized stream → [N, seq_len+1] rows (the stream is
    assumed to carry its own document separators)."""
    row = seq_len + 1
    n = stream.size // row
    if n == 0:
        raise ValueError(
            f"corpus too small: {stream.size} tokens < one row of {row}"
        )
    return stream[: n * row].reshape(n, row).astype(np.int32)


def pack_documents(
    docs: list, seq_len: int, eos_id: Optional[int] = 0
) -> np.ndarray:
    """Concatenate docs (EOS-separated) → [N, seq_len+1] int32 rows.

    ``eos_id=None`` concatenates without inserting separators (for docs
    that already end in their tokenizer's EOS). The ragged tail
    (< seq_len+1 tokens) is dropped — padding would waste MXU cycles on
    masked positions.
    """
    joined: list[np.ndarray] = []
    for d in docs:
        d = np.asarray(d, np.int32).reshape(-1)
        joined.append(d)
        if eos_id is not None and (d.size == 0 or d[-1] != eos_id):
            joined.append(np.asarray([eos_id], np.int32))
    stream = np.concatenate(joined) if joined else np.zeros((0,), np.int32)
    return _reshape_stream(stream, seq_len)


def batches(
    rows: np.ndarray,  # [N, seq_len+1]
    batch_size: int,
    seed: int = 0,
    epochs: Optional[int] = None,  # None = loop forever
) -> Iterator[dict]:
    """Shuffled epoch iterator → {tokens, targets, mask} host batches.

    Targets are the packed rows shifted by one (no wraparound garbage —
    the +1 column exists exactly for this). Mask is all-ones: packing
    leaves no padding. The partial tail batch of each epoch is dropped
    (static shapes: every batch recompiles nothing).
    """
    n = rows.shape[0]
    if n < batch_size:
        raise ValueError(f"corpus has {n} rows < batch size {batch_size}")
    rng = np.random.default_rng(seed)
    epoch = 0
    while epochs is None or epoch < epochs:
        order = rng.permutation(n)
        for i in range(0, n - batch_size + 1, batch_size):
            chunk = rows[order[i : i + batch_size]]
            tokens = chunk[:, :-1].astype(np.int32)
            yield {
                "tokens": tokens,
                "targets": chunk[:, 1:].astype(np.int32),
                "mask": np.ones_like(tokens),
            }
        epoch += 1


def prefetch_to_device(
    it: Iterator[dict], size: int = 2, sharding=None
) -> Iterator[dict]:
    """Double-buffered host→device transfer: keeps ``size`` batches in
    flight so the copy of batch k+1 overlaps step k's compute.

    ``sharding``: a NamedSharding for the [B, T] batch leaves (dp/fsdp
    sharded); None puts on the default device.
    """
    import collections

    import jax

    def put(b):
        if sharding is None:
            return jax.device_put(b)
        return jax.device_put(b, jax.tree.map(lambda _: sharding, b))

    buf = collections.deque()
    for b in it:
        buf.append(put(b))
        if len(buf) >= size:
            yield buf.popleft()
    while buf:
        yield buf.popleft()
