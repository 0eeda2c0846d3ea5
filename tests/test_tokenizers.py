"""Tokenizers + offline data-prep tools (SURVEY.md §2a rows 4–5).

The reference relied on downloaded tokenizer assets; here both
tokenizers are pure-python and trainable offline, so these tests build
real vocabularies from in-test corpora and assert lossless (BPE) /
faithful (WordPiece) round-trips, then drive the prep tools end-to-end
into the exact formats the data loaders consume.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from tensorflow_examples_tpu.data.tokenizers import (
    ByteLevelBPE,
    WordPiece,
    bytes_to_unicode,
)

CORPUS = [
    "The quick brown fox jumps over the lazy dog. "
    "The dog was not amused, the fox was very pleased.\n",
    "Training language models requires tokenized text; tokenizers turn "
    "text into integers and back again without losing information.\n",
    "Numbers like 1234 and 3.14159, punctuation?! And unicode: café, "
    "naïve, 中文, emoji \U0001f680✨.\n",
]


def test_byte_unicode_map_reversible():
    m = bytes_to_unicode()
    assert len(m) == 256
    assert len(set(m.values())) == 256  # bijective


class TestByteLevelBPE:
    @pytest.fixture(scope="class")
    def bpe(self):
        return ByteLevelBPE.train(CORPUS, vocab_size=400)

    def test_roundtrip_lossless(self, bpe):
        for text in CORPUS + [
            "completely unseen text with weird   spacing\t\tand\nnewlines",
            "bytes outside the corpus: üñîçødè \U0001f4af",
            "",
            " leading and trailing ",
        ]:
            ids = bpe.encode(text)
            assert bpe.decode(ids) == text

    def test_merges_actually_compress(self, bpe):
        text = CORPUS[0]
        ids = bpe.encode(text)
        assert len(ids) < len(text.encode("utf-8"))  # better than bytes

    def test_eot_token(self, bpe):
        assert bpe.eot_id == bpe.vocab_size - 1
        assert bpe.decode([bpe.eot_id]) == ""  # specials dropped on decode

    def test_save_load_identical(self, bpe, tmp_path):
        bpe.save(str(tmp_path))
        reloaded = ByteLevelBPE.from_dir(str(tmp_path))
        for text in CORPUS:
            assert reloaded.encode(text) == bpe.encode(text)
        assert reloaded.vocab_size == bpe.vocab_size

    def test_gpt2_file_format(self, tmp_path):
        """Hand-written vocab.json/merges.txt in the published format."""
        vocab = {c: i for i, c in enumerate(map(chr, range(33, 127)))}
        vocab["he"] = len(vocab)
        vocab["hel"] = len(vocab)
        with open(tmp_path / "vocab.json", "w") as f:
            json.dump(vocab, f)
        with open(tmp_path / "merges.txt", "w") as f:
            f.write("#version: 0.2\nh e\nhe l\n")
        tok = ByteLevelBPE.from_dir(str(tmp_path))
        ids = tok.encode("hello")
        assert [tok.decoder[i] for i in ids] == ["hel", "l", "o"]
        assert tok.decode(ids) == "hello"


class TestWordPiece:
    @pytest.fixture(scope="class")
    def wp(self):
        return WordPiece.build(CORPUS, vocab_size=300)

    def test_tokenize_known_words(self, wp):
        pieces = wp.tokenize("The quick fox")
        assert pieces  # non-empty
        rebuilt = wp.decode([wp.vocab[p] for p in pieces])
        assert rebuilt == "the quick fox"  # lowercased, faithful

    def test_subword_fallback(self, wp):
        # Unseen word splits into known subpieces or [UNK], never crashes.
        pieces = wp.tokenize("zzgrxq unbelievabletokenization")
        assert all(p == "[UNK]" or p.lstrip("#") for p in pieces)

    def test_encode_schema(self, wp):
        f = wp.encode("the fox was pleased", "the dog was not", seq_len=32)
        assert f["tokens"].shape == (32,)
        assert f["attention_mask"].shape == (32,)
        assert f["token_type_ids"].shape == (32,)
        n = int(f["attention_mask"].sum())
        assert f["tokens"][0] == wp.vocab["[CLS]"]
        seps = np.where(f["tokens"][:n] == wp.vocab["[SEP]"])[0]
        assert len(seps) == 2  # pair input → two separators
        # Type ids: 0 through the first [SEP], 1 after it.
        assert f["token_type_ids"][seps[0]] == 0
        assert f["token_type_ids"][seps[0] + 1] == 1
        assert (f["tokens"][n:] == wp.vocab["[PAD]"]).all()

    def test_truncation(self, wp):
        long = "fox " * 100
        f = wp.encode(long, long, seq_len=16)
        assert int(f["attention_mask"].sum()) == 16

    def test_vocab_file_roundtrip(self, wp, tmp_path):
        path = str(tmp_path / "vocab.txt")
        wp.save(path)
        reloaded = WordPiece.from_vocab_file(path)
        text = "tokenizers turn text into integers"
        assert reloaded.tokenize(text) == wp.tokenize(text)


# ----------------------------------------------------------------- tools


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_tool(script, *args):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", script), *args],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout


def test_prepare_lm_end_to_end(tmp_path):
    corpus = tmp_path / "corpus.txt"
    corpus.write_text(" ".join(CORPUS) * 30)
    out = tmp_path / "lm"
    _run_tool(
        "prepare_lm.py",
        f"--input={corpus}",
        f"--out_dir={out}",
        "--train_vocab=320",
        "--val_fraction=0.1",
    )
    from tensorflow_examples_tpu.data.sources import load_lm_tokens

    ds = load_lm_tokens(str(out), "train", seq_len=32, vocab_size=320)
    toks = ds.arrays["tokens"]
    assert toks.shape[1] == 33 and toks.shape[0] > 0
    # Decode a window back: must be real corpus text, not garbage.
    tok = ByteLevelBPE.from_dir(str(out))
    text = tok.decode(toks[0])
    assert "fox" in text or "token" in text or "Number" in text
    assert os.path.exists(out / "val.bin")


def test_prepare_glue_end_to_end(tmp_path):
    tsv = tmp_path / "train.tsv"
    rows = ["sentence\tlabel"]
    for i in range(12):
        rows.append(f"this movie was {'great fun' if i % 2 else 'a dull mess'}\t{i % 2}")
    tsv.write_text("\n".join(rows) + "\n")
    out = tmp_path / "glue"
    _run_tool(
        "prepare_glue.py",
        "--task=sst2",
        f"--input={tsv}",
        "--split=train",
        f"--out_dir={out}",
        "--build_vocab=200",
        "--seq_len=24",
    )
    from tensorflow_examples_tpu.data.sources import load_glue

    ds = load_glue(str(out), "sst2", "train", seq_len=24)
    a = ds.arrays
    assert a["tokens"].shape == (12, 24)
    assert a["attention_mask"].shape == (12, 24)
    assert a["token_type_ids"].shape == (12, 24)
    assert set(np.asarray(a["label"]).tolist()) == {0, 1}


def test_prepare_glue_pair_task(tmp_path):
    tsv = tmp_path / "train.tsv"
    rows = ["index\tsentence1\tsentence2\tlabel"]
    for i in range(6):
        rows.append(f"{i}\tthe fox jumped\tthe dog slept\t{'entailment' if i % 2 else 'not_entailment'}")
    tsv.write_text("\n".join(rows) + "\n")
    out = tmp_path / "glue"
    _run_tool(
        "prepare_glue.py",
        "--task=rte",
        f"--input={tsv}",
        "--split=validation",
        f"--out_dir={out}",
        "--build_vocab=150",
        "--seq_len=32",
    )
    d = np.load(out / "rte_validation.npz")
    assert d["token_type_ids"].max() == 1  # pair → second segment present
    assert set(d["label"].tolist()) == {0, 1}


def test_stdlib_re_fallback_pattern_is_lossless():
    """The `re` fallback pre-tokenizer (used only when the `regex`
    package is absent) must still cover every character — underscores
    are the trap: "_" is \\w but not a letter class member."""
    import re

    # Mirror of the fallback pattern in data/tokenizers.py.
    pat = re.compile(
        r"'s|'t|'re|'ve|'m|'ll|'d| ?[^\W\d_]+| ?\d+| ?(?:[^\s\w]|_)+"
        r"|\s+(?!\S)|\s+",
        re.UNICODE,
    )
    for text in [
        "foo_bar",
        "__init__ = a_1 + b_2",
        "mixed _lead and trail_ cases",
        "the quick brown fox! 42 times?",
    ]:
        assert "".join(pat.findall(text)) == text, text
