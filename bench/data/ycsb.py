"""YCSB-fakeit customer documents and their predicate pool (CIAO §VII-B).

A record is the nested customer document of the YCSB-JSON benchmark,
which fakeit generates: names, ids, balance, birth date, contact and
URL fields at the top level, beside arrays and objects (``devices``,
``linked_devices``, ``address`` with a ``prev_address``, ``children``,
``visited_places``, ``order_list``).  About 1.3 KB of JSON a record.
The keys that Table II's predicates name (``isActive``,
``linear_score``, ``weighted_score``, ``age_by_group``,
``phone_country``, ``age_group``, ``url_domain``, ``url_site``,
``email``) keep the types and value distributions of the program's
``data/datasets.py:ycsb_record``; the rest follow the fakeit model, with
the element counts and vocabularies set by hand (listed under
``assumed`` in the configuration).  No predicate names a nested field,
so those stay in a record's raw remainder, and each nested field of a
row is drawn from 2^16 variants made once per table.  Drawn column by
column, so that a million records take seconds, not minutes.  The pool
is Table II's YCSB row, as ``predicate_pool("ycsb")`` builds it, kept
here as data so that the benchmark's yardstick does not move with the
program.
"""
from __future__ import annotations

import numpy as np

from .render import Nested, Table, array, cat, digits, obj, uuid, vocab

WORDS = (
    "delicious amazing terrible friendly slow fast cozy loud quiet great "
    "awful fresh stale crowded empty cheap pricey clean dirty lovely bland "
    "spicy sweet salty crispy tender juicy dry warm cold attentive rude"
).split()
DOMAINS = "com org net io edu gov co uk de jp fr ca".split()
SITES = (
    "alpha beta gamma delta epsilon zeta eta theta iota kappa lambdaone mutual"
).split()
COUNTRIES = ["US", "CN", "IN"]
AGE_GROUPS = ["child", "young", "adult", "senior"]
NAMES = (
    "Ada Alan Alice Amir Ana Bela Boris Carla Chen Dara Elif Emil Eva Femi "
    "Hana Ivan Jana Jorge Kai Lena Lior Maya Mei Nia Omar Pia Ravi Rosa "
    "Sami Tara Uma Yuki"
).split()
SURNAMES = (
    "Abbott Bauer Brennan Castillo Dubois Eriksen Fischer Garcia Haddad "
    "Ivanova Jensen Kovacek Larsen Moreau Nakamura Novak Okafor Petrov "
    "Quinn Rossi Schmidt Tanaka Ueda Vargas Weber Yilmaz Zhang Ziemann"
).split()
CITIES = (
    "Springfield Riverton Lakeview Fairmont Ashford Brookfield Clearwater "
    "Dunmore Eastwood Glenhaven Harborview Kingsport Maplewood Northgate "
    "Oakridge Pinecrest Redmond Stonebridge Westfield Willowbrook"
).split()
STREETS = (
    "Main Oak Pine Maple Cedar Elm Lake Hill Park River Mill Church"
).split()
STREET_KINDS = "Street Avenue Road Lane Drive Court Way Boulevard".split()
NATIONS = (
    "France Germany Japan Brazil Canada India China Kenya Mexico Norway "
    "Spain Turkey Vietnam Chile Egypt Poland"
).split()
DEVICES = "Phone Tablet Laptop Watch Desktop TV".split()
PROTOCOLS = ["http", "https"]
GENDERS = ["F", "M"]


def _address(rng: np.random.Generator, n: int) -> dict[str, np.ndarray]:
    pick = lambda words: vocab(words, rng.integers(0, len(words), n))
    return {
        "street": cat(digits(rng.integers(1, 9999, n)), b" ", pick(STREETS),
                      b" ", pick(STREET_KINDS)),
        "city": pick(CITIES),
        "zip": digits(rng.integers(0, 100000, n), 5),
        "country": pick(NATIONS),
    }


VARIANTS = 1 << 16      # of each nested field, per table


def _nested(rng: np.random.Generator, n: int) -> dict[str, np.ndarray]:
    """``n`` variants of each nested field."""
    pick = lambda words: vocab(words, rng.integers(0, len(words), n))
    count = lambda lo, hi: rng.integers(lo, hi + 1, n)
    device = lambda: cat(pick(COUNTRIES), b"-", pick(DEVICES), b"-",
                         digits(rng.integers(0, 10**6, n), 6))
    return {
        "devices": array([device() for _ in range(3)], count(1, 3)),
        "linked_devices": array([device() for _ in range(2)], count(0, 2)),
        "address": obj(**_address(rng, n),
                       prev_address=obj(**_address(rng, n))),
        "children": array(
            [obj(first_name=pick(NAMES), gender=pick(GENDERS),
                 age=rng.integers(0, 18, n)) for _ in range(4)],
            count(0, 4)),
        "visited_places": array(
            [obj(country=pick(NATIONS),
                 cities=array([pick(CITIES) for _ in range(3)], count(1, 3)))
             for _ in range(3)],
            count(1, 3)),
        "order_list": array(
            [obj(order_id=cat(b"order:::", uuid(rng, n)),
                 product_id=cat(b"product:::", digits(
                     rng.integers(0, 10**6, n), 6)),
                 quantity=rng.integers(1, 10, n))
             for _ in range(4)],
            count(1, 4)),
    }


def generate(n: int, seed: int) -> Table:
    rng = np.random.default_rng(seed)
    pick = lambda words, **kw: vocab(
        words, rng.choice(len(words), size=n, **kw))
    dom = pick(DOMAINS)
    site = pick(SITES)
    first = pick(WORDS)
    url_site = cat(b"www.", site, b".", dom)
    protocol = pick(PROTOCOLS)
    variants = _nested(rng, min(n, VARIANTS))
    nested = {k: Nested(v[rng.integers(0, len(v), n)])
              for k, v in variants.items()}
    return Table({
        "doc_id": cat(b"customer:::", uuid(rng, n)),
        "gid": uuid(rng, n),
        "first_name": np.strings.capitalize(first),
        "middle_name": pick(NAMES),
        "last_name": pick(SURNAMES),
        "ballance_current": cat(b"$", digits(rng.integers(0, 10000, n)),
                                b".", digits(rng.integers(0, 100, n), 2)),
        "dob": cat(digits(rng.integers(1940, 2018, n)), b"-",
                   digits(rng.integers(1, 13, n), 2), b"-",
                   digits(rng.integers(1, 29, n), 2)),
        "email": cat(first, digits(rng.integers(0, 999, n)), b"@", site,
                     b".", dom),
        "isActive": rng.random(n) < 0.5,
        "linear_score": rng.integers(0, 100, n),
        "weighted_score": rng.integers(0, 100, n),
        "phone_country": pick(COUNTRIES, p=[0.5, 0.3, 0.2]),
        "phone_by_country": cat(b"+", digits(rng.integers(1, 99, n)), b" ",
                                digits(rng.integers(100, 1000, n)), b"-",
                                digits(rng.integers(10**6, 10**7, n))),
        "age_group": pick(AGE_GROUPS),
        "age_by_group": rng.integers(0, 100, n),
        "url_protocol": protocol,
        "url_site": url_site,
        "url_domain": dom,
        "url": cat(protocol, b"://", url_site, b"/", pick(WORDS), b"/",
                   pick(WORDS), b"?id=", digits(rng.integers(0, 10**6, n))),
        **nested,
    })


def pool() -> list[tuple]:
    """Table II's YCSB predicates as ``(kind, key, value)`` clauses of one
    term each."""
    out: list[tuple] = [("key_value", "isActive", b) for b in (True, False)]
    for key in ("linear_score", "weighted_score", "age_by_group"):
        out += [("key_value", key, v) for v in range(100)]
    out += [("exact", "phone_country", c) for c in COUNTRIES]
    out += [("exact", "age_group", g) for g in AGE_GROUPS]
    out += [("substring", "url_domain", d) for d in DOMAINS]
    out += [("substring", "url_site", f"www.{s}.") for s in SITES]
    out += [("substring", "email", "@"), ("presence", "email", None)]
    return out
