"""Scale-suite conformance at test size: engine answers vs goldens
derived independently from the corpus model (goldens by reasoned
derivation, not hand-typed).
"""

import os
import sys


def test_scale_suite_conformance():
    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from benchmarks.movie_corpus import generate
    from benchmarks.scale_suite import load, run_suite

    corpus, server, _ = load(15_000)
    res = run_suite(corpus, server, repeat=1)
    bad = {k: v for k, v in res.items() if not v["ok"]}
    assert not bad, f"conformance failures: {bad}"
    # sanity: the corpus actually exercised non-trivial sizes
    assert res["films_of_genre"]["n"] > 50
    assert res["directors_of_genre_2hop"]["n"] > 20


def test_corpus_determinism():
    from benchmarks.movie_corpus import generate

    c1, rdf1 = generate(5000, seed=7)
    c2, rdf2 = generate(5000, seed=7)
    assert rdf1 == rdf2
    assert c1.film_rating == c2.film_rating
