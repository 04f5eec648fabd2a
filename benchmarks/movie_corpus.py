"""Deterministic movie-style corpus generator (the 1million analog).

Mirrors the shape of the reference's benchmark dataset
(systest/1million/1million_test.go, benchmarks repo 1million.rdf.gz):
directors direct films, films carry genres and release dates, actors
star in films; names are exact/term-indexed strings.

The generator returns BOTH the RDF stream and a plain-Python graph model,
so conformance goldens are DERIVED independently of the engine
(no hand-typed goldens) — any query the suite
runs is answered twice: once by the engine, once by direct dict walks
here, and the two must agree.

Scale knob = target edge count; 1M edges ≈ 30k films / 6k directors /
60k actors at the default fan-outs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Set, Tuple

import numpy as np

GENRES = [
    "Action", "Comedy", "Drama", "Horror", "Romance", "Thriller",
    "Documentary", "Animation", "Crime", "Fantasy", "Mystery", "Western",
]

SCHEMA = """
name: string @index(exact, term) .
initial_release_date: datetime @index(year) .
genre: [uid] @reverse .
director.film: [uid] @reverse @count .
starring: [uid] @reverse .
rating: float @index(float) .
"""


@dataclass
class Corpus:
    # uid maps
    genres: Dict[str, int] = field(default_factory=dict)
    directors: Dict[int, str] = field(default_factory=dict)
    films: Dict[int, str] = field(default_factory=dict)
    actors: Dict[int, str] = field(default_factory=dict)
    # edges
    film_genres: Dict[int, List[int]] = field(default_factory=dict)
    director_films: Dict[int, List[int]] = field(default_factory=dict)
    actor_films: Dict[int, List[int]] = field(default_factory=dict)
    film_year: Dict[int, int] = field(default_factory=dict)
    film_rating: Dict[int, float] = field(default_factory=dict)
    n_edges: int = 0

    # -- derived goldens (independent of the engine) ----------------------

    def films_of_genre(self, genre: str) -> List[int]:
        g = self.genres[genre]
        return sorted(
            f for f, gs in self.film_genres.items() if g in gs
        )

    def directors_of_genre(self, genre: str) -> List[int]:
        """Directors with at least one film in the genre (2-hop)."""
        films = set(self.films_of_genre(genre))
        return sorted(
            d
            for d, fs in self.director_films.items()
            if films.intersection(fs)
        )

    def films_in_year(self, year: int) -> List[int]:
        return sorted(f for f, y in self.film_year.items() if y == year)

    def costars(self, actor_uid: int) -> List[int]:
        """Actors sharing a film with the given actor (2-hop via reverse)."""
        films = set(self.actor_films.get(actor_uid, []))
        out: Set[int] = set()
        for a, fs in self.actor_films.items():
            if a != actor_uid and films.intersection(fs):
                out.add(a)
        return sorted(out)

    def actors_of_director(self, d: int) -> List[int]:
        """3-hop: director -> films -> starring actors."""
        films = set(self.director_films.get(d, []))
        out: Set[int] = set()
        for a, fs in self.actor_films.items():
            if films.intersection(fs):
                out.add(a)
        return sorted(out)

    def genres_by_film_count(self) -> List[tuple]:
        """(genre uid, #films) sorted by count desc then uid."""
        counts = {g: 0 for g in self.genres.values()}
        for gs in self.film_genres.values():
            for g in gs:
                counts[g] += 1
        return sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))

    def prolific_directors(self, min_films: int) -> List[int]:
        return sorted(
            d for d, fs in self.director_films.items() if len(fs) >= min_films
        )

    def top_rated(self, n: int) -> List[int]:
        return [
            f
            for f, _ in sorted(
                self.film_rating.items(), key=lambda kv: (-kv[1], kv[0])
            )[:n]
        ]


def generate(target_edges: int = 1_000_000, seed: int = 42) -> Tuple[Corpus, List[str]]:
    """Returns (corpus model, rdf lines). Edge count ≈ target_edges."""
    rng = np.random.default_rng(seed)
    c = Corpus()
    rdf: List[str] = []
    uid = 0x1000

    def nxt() -> int:
        nonlocal uid
        uid += 1
        return uid

    for g in GENRES:
        u = nxt()
        c.genres[g] = u
        rdf.append(f'<0x{u:x}> <name> "{g}" .')
        c.n_edges += 1

    # fan-outs: each film -> ~2 genres + 1 date + 1 rating + 1 name = ~5
    # each director -> ~5 films; each actor -> ~3 films
    # edges per film ≈ 5 + (1/5 dir name) + 2 starring + ...; solve approx:
    n_films = max(10, target_edges // 9)
    n_directors = max(3, n_films // 5)
    n_actors = max(5, n_films * 2 // 3)

    for i in range(n_directors):
        u = nxt()
        c.directors[u] = f"Director {i}"
        rdf.append(f'<0x{u:x}> <name> "Director {i}" .')
        c.director_films[u] = []
        c.n_edges += 1

    for i in range(n_actors):
        u = nxt()
        c.actors[u] = f"Actor {i}"
        rdf.append(f'<0x{u:x}> <name> "Actor {i}" .')
        c.actor_films[u] = []
        c.n_edges += 1

    dirs = list(c.directors)
    actors = list(c.actors)
    genre_uids = list(c.genres.values())

    for i in range(n_films):
        u = nxt()
        title = f"Film {i} of the {GENRES[i % len(GENRES)]}"
        c.films[u] = title
        rdf.append(f'<0x{u:x}> <name> "{title}" .')
        year = 1950 + int(rng.integers(0, 75))
        c.film_year[u] = year
        rdf.append(
            f'<0x{u:x}> <initial_release_date> '
            f'"{year}-{1 + int(rng.integers(0, 12)):02d}-01" .'
        )
        rating = round(float(rng.uniform(1.0, 10.0)), 2)
        c.film_rating[u] = rating
        rdf.append(f'<0x{u:x}> <rating> "{rating}"^^<xs:float> .')
        c.n_edges += 3
        gs = rng.choice(genre_uids, size=1 + int(rng.integers(0, 2)), replace=False)
        c.film_genres[u] = [int(g) for g in gs]
        for g in gs:
            rdf.append(f"<0x{u:x}> <genre> <0x{int(g):x}> .")
            c.n_edges += 1
        d = int(dirs[int(rng.integers(0, len(dirs)))])
        c.director_films[d].append(u)
        rdf.append(f"<0x{d:x}> <director.film> <0x{u:x}> .")
        c.n_edges += 1
        stars = rng.choice(len(actors), size=2, replace=False)
        for si in stars:
            a = int(actors[int(si)])
            c.actor_films[a].append(u)
            rdf.append(f"<0x{a:x}> <starring> <0x{u:x}> .")
            c.n_edges += 1

    return c, rdf
