"""The generated tokenizer gives every id a visible delta and prompts an
exact token count, through the program's own tokenizer loader."""

from benchmark import tokenizer


def test_every_id_is_visible_and_prompts_are_exact(tmp_path):
    from dstack_tpu.serve.tokenizer import load_tokenizer

    d = tokenizer.write_tokenizer_dir(str(tmp_path / "tok"), 3000)
    tok = load_tokenizer(d)
    assert tok.eos_id is None and tok.bos_id is None
    ids = list(range(1, 3000, 7)) + [2999]
    text = tokenizer.text_of(ids)
    assert tok.encode(text) == ids
    # the server streams by re-decoding the accumulated ids: every new id
    # must lengthen the text by one visible word
    sent = ""
    for n in range(1, 40):
        out = tok.decode(ids[:n])
        delta = out[len(sent):]
        assert tokenizer.ids_of(delta) == [ids[n - 1]]
        sent = out
    assert tokenizer.ids_of(tok.decode(list(range(3000)))) == list(range(3000))


def test_rewritten_only_when_the_size_differs(tmp_path):
    import os

    d = tokenizer.write_tokenizer_dir(str(tmp_path / "tok"), 300)
    m = os.path.getmtime(os.path.join(d, "tokenizer.json"))
    tokenizer.write_tokenizer_dir(d, 300)
    assert os.path.getmtime(os.path.join(d, "tokenizer.json")) == m


def test_a_foreign_delta_is_refused():
    import pytest

    with pytest.raises(ValueError):
        tokenizer.ids_of("w1 hello")
