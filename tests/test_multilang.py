"""Multi-language fulltext stemming (ref tok.go FullTextTokenizer{lang},
bleve per-language analyzers).
"""

import pytest

from dgraph_tpu.api.server import Server
from dgraph_tpu.tok.stemmers import REGISTRY, lang_base
from dgraph_tpu.tok.tok import FulltextTokenizer
from dgraph_tpu.types.types import TypeID, Val


def _toks(text, lang=""):
    t = FulltextTokenizer()
    return {b[1:].decode() for b in t.tokens(Val(TypeID.STRING, text), lang)}


def test_lang_base():
    assert lang_base("fr-CA") == "fr"
    assert lang_base("pt_BR") == "pt"
    assert lang_base("") == ""


def test_spanish_stems_and_stopwords():
    got = _toks("las bibliotecas nacionales", "es")
    # stopword 'las' dropped; plural endings stripped
    assert "las" not in got
    assert _toks("biblioteca nacional", "es") & got


def test_french_stems():
    a = _toks("les nations européennes", "fr")
    b = _toks("nation européenne", "fr")
    assert "les" not in a
    assert a & b


def test_german_stems():
    a = _toks("die Bibliotheken", "de")
    b = _toks("Bibliothek", "de")
    assert a & b


def test_russian_stopwords():
    got = _toks("и все книги", "ru")
    assert "и" not in got


def test_unknown_lang_falls_back():
    # no stemmer: words tokenize as-is through the EN pipeline
    assert _toks("running waters", "xx")


def test_engine_lang_aware_fulltext():
    s = Server()
    s.alter("bio: string @index(fulltext) @lang .")
    t = s.new_txn()
    t.mutate_rdf(
        set_rdf=(
            '<0x1> <bio> "las bibliotecas nacionales"@es .\n'
            '<0x2> <bio> "national libraries"@en .'
        ),
        commit_now=True,
    )
    # Spanish query form matches the Spanish-stemmed document
    out = s.query('{ q(func: alloftext(bio@es, "biblioteca nacional")) { uid } }')
    assert [x["uid"] for x in out["data"]["q"]] == ["0x1"]
    out = s.query('{ q(func: alloftext(bio@en, "library national")) { uid } }')
    assert [x["uid"] for x in out["data"]["q"]] == ["0x2"]


def test_cjk_fulltext_bigrams():
    """CJK analyzer (ref tok.go bleve cjk analyzer for zh/ja/ko):
    ideograph runs index as overlapping
    bigrams, searchable via alloftext with @lang."""
    from dgraph_tpu.api.server import Server

    s = Server()
    s.alter("title: string @index(fulltext) @lang .")
    t = s.new_txn()
    t.mutate_rdf(set_rdf='''
        <0x1> <title> "数据库系统"@zh .
        <0x2> <title> "分布式计算"@zh .
        <0x3> <title> "データベース"@ja .
    ''')
    t.commit()
    out = s.query('{ q(func: alloftext(title@zh, "数据")) { uid } }')
    assert [r["uid"] for r in out["data"]["q"]] == ["0x1"]
    out = s.query('{ q(func: alloftext(title@zh, "计算")) { uid } }')
    assert [r["uid"] for r in out["data"]["q"]] == ["0x2"]
    out = s.query('{ q(func: alloftext(title@ja, "データ")) { uid } }')
    assert [r["uid"] for r in out["data"]["q"]] == ["0x3"]
    # a bigram that spans nothing stored must not match
    out = s.query('{ q(func: alloftext(title@zh, "系统计算")) { uid } }')
    assert out["data"]["q"] == []


def test_decrypt_cli_roundtrip(tmp_path):
    """dgraph decrypt (ref dgraph/cmd/decrypt/decrypt.go:47)."""
    pytest.importorskip("cryptography")
    import gzip
    import os

    from dgraph_tpu.cli import main as cli_main
    from dgraph_tpu.enc import enc

    key = os.urandom(32)
    kf = tmp_path / "key"
    kf.write_bytes(key)
    plain = b"<0x1> <name> \"secret export\" .\n" * 50
    encf = tmp_path / "export.rdf"
    encf.write_bytes(enc.encrypt_stream(plain, key))
    outf = tmp_path / "out.rdf.gz"
    cli_main([
        "decrypt", "-f", str(encf), "-o", str(outf),
        "--encryption-key-file", str(kf),
    ])
    assert gzip.decompress(outf.read_bytes()) == plain
