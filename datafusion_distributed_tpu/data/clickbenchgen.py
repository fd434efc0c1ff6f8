"""Synthetic ClickBench `hits` dataset generator.

The reference downloads the real ClickBench parquet (~14 GB,
`/root/reference/benchmarks/src/datasets/clickbench.rs`) for its plan and
correctness suites (`tests/clickbench_plans_test.rs`,
`tests/clickbench_correctness_test.rs`). No network egress here, so the
table is generated: the 25 columns the 43 queries touch, with spec-shaped
domains (EventTime as epoch seconds in July 2013, mostly-empty SearchPhrase
/ MobilePhoneModel, URLs with 'google' substrings for the LIKE queries,
zero-heavy AdvEngineID, ±1 TraficSourceID). Correctness tests compare
against a pandas oracle over the same generated rows.

``SearchPhrase`` follows the real table's law where a figure is published:
``SEARCH_PHRASES`` = 6,019,102 distinct non-empty phrases (ClickBench q5's
answer over the real `hits`, 6,019,103, less the empty string), drawn by
Zipf with exponent ``ZIPF_EXPONENT`` (assumed), and the empty string in
``EMPTY_PHRASE_SHARE`` of the rows (assumed). A phrase is a function of its
popularity rank: 1-5 words (lengths assumed), the rank written in base
``len(words)`` over pseudo-words the seed shuffles, after filler words that
are never pseudo-words, so two ranks never share a text. Only the ranks a
table draws are written out: 20,000 rows make a few thousand phrases, 50M
rows some 2.1M.

Every column is made by numpy draws and Arrow kernels over whole columns:
a string column is a vocabulary and one code a row (`pyarrow.compute.take`),
never a Python object a row. The 64-bit ids (``WatchID``, ``UserID``,
``URLHash``, ``RefererHash``) are drawn below 2^31, where the real ones are
not, so that 32-bit device columns hold them. Deterministic by seed.
"""

from __future__ import annotations

import numpy as np

_EPOCH_2013_07_01 = 15887  # days since epoch
_SECS_2013_07_01 = _EPOCH_2013_07_01 * 86400
_DAYS = 31

#: distinct non-empty search phrases of the real `hits` (ClickBench q5,
#: COUNT(DISTINCT "SearchPhrase") = 6,019,103, less the empty string)
SEARCH_PHRASES = 6_019_102
#: share of rows whose SearchPhrase is the empty string (assumed)
EMPTY_PHRASE_SHARE = 0.72
#: Zipf exponent of the non-empty phrases' popularity (assumed)
ZIPF_EXPONENT = 1.0
#: words a phrase has, fillers included (assumed)
PHRASE_WORDS = (1, 5)

_CONSONANTS = "bdfgklmnprstvz"
_VOWELS = "aeiou"
# two syllables a pseudo-word: 4,900 words, so a rank is one or two of them
_PSEUDO_WORDS = [a + b + c + d for a in _CONSONANTS for b in _VOWELS
                 for c in _CONSONANTS for d in _VOWELS]
_FILLERS = ["how", "to", "the", "for", "in", "buy", "cheap", "best",
            "online", "free", "new", "watch", "download", "weather", "news",
            "movie", "maps", "hotel", "price", "review", "2013", "moscow",
            "photo", "video", "music", "games", "recipe", "tickets", "forum",
            "cars"]
assert not set(_FILLERS) & set(_PSEUDO_WORDS)

_PHONE_MODELS = ["", "iPhone 5", "Galaxy S4", "Lumia 920", "Xperia Z",
                 "Nexus 4"]
_PHONE_MODEL_P = [0.5, 0.1, 0.1, 0.1, 0.1, 0.1]
_URL_HOSTS = ["http://example.com", "http://google.ru/search",
              "http://news.site", "http://shop.online", "http://maps.app",
              "http://video.portal", "http://maps.google.com/dir",
              "http://forum.board", "http://mail.box", "http://blog.spot"]
_URL_PATHS = 5000
_TITLES = ["Home", "Search results - Google", "News", "Shop",
           "Google Maps", "Video", "Forum", "Mail", "Blog", "Weather", ""]
_REFERERS = ["", "http://google.ru/", "http://direct.link/",
             "http://social.net/", "http://mail.box/"]
_REFERER_P = [2 / 6, 1 / 6, 1 / 6, 1 / 6, 1 / 6]


def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over uint64: a fixed hash of each value."""
    x = x.astype(np.uint64)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def phrase_texts(ranks: np.ndarray, seed: int):
    """-> pyarrow string array: the phrase of each popularity rank
    (1-based, at most `SEARCH_PHRASES`), a function of (rank, seed)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    ranks = np.asarray(ranks, dtype=np.int64)
    base = len(_PSEUDO_WORDS)
    order = np.random.default_rng(seed).permutation(base)
    words = pa.array([_PSEUDO_WORDS[i] for i in order] + _FILLERS)
    digits = np.where(ranks < base, 1, 2)
    h = _mix(ranks)
    lo, hi = PHRASE_WORDS
    length = np.maximum(lo + (h % np.uint64(hi - lo + 1)).astype(np.int64),
                        digits)
    fillers = length - digits
    parts = []
    for pos in range(hi):
        # fillers first, then the rank's digits, most significant first
        is_filler = pos < fillers
        digit_pos = pos - fillers  # 0 = the first digit word
        shift = np.where(digits - 1 - digit_pos == 1, base, 1)
        digit = (ranks // shift) % base
        filler = base + (_mix(h + np.uint64(pos + 1))
                         % np.uint64(len(_FILLERS))).astype(np.int64)
        index = np.where(is_filler, filler, digit)
        absent = pos >= length
        parts.append(pc.take(words, pa.array(index, mask=absent)))
    return pc.binary_join_element_wise(*parts, " ", null_handling="skip")


def search_phrase_codes(rows: int, rng) -> np.ndarray:
    """-> [rows] int64: 0 for the empty string, else the phrase's
    popularity rank, Zipf(`ZIPF_EXPONENT`) over `SEARCH_PHRASES`."""
    nonempty = rng.random(rows) >= EMPTY_PHRASE_SHARE
    weights = np.arange(1, SEARCH_PHRASES + 1, dtype=np.float64) ** (
        -ZIPF_EXPONENT)
    cdf = np.cumsum(weights)
    cdf /= cdf[-1]
    ranks = np.zeros(rows, dtype=np.int64)
    drawn = np.searchsorted(cdf, rng.random(int(nonempty.sum())),
                            side="right") + 1
    ranks[nonempty] = np.minimum(drawn, SEARCH_PHRASES)
    return ranks


def _from_vocabulary(vocabulary, codes: np.ndarray):
    """A string column: ``vocabulary[codes[i]]`` a row, by Arrow's take."""
    import pyarrow as pa
    import pyarrow.compute as pc

    return pc.take(pa.array(vocabulary, type=pa.string()),
                   pa.array(codes.astype(np.int32, copy=False)))


def gen_clickbench(rows: int = 100_000, seed: int = 0):
    """Generate the `hits` table as a pyarrow Table."""
    return _hits(rows, np.random.default_rng(seed), seed)


def gen_clickbench_partitions(rows: int, seed: int, partitions: int) -> list:
    """`hits` of ``rows`` rows as ``partitions`` row partitions, pyarrow
    Tables of ``ceil(rows / partitions)`` rows (the last one the rest), as
    a table split over the chips of a host is registered
    (`io/parquet.py Partitions`). Partition i is drawn from ``(seed, i)``
    on a thread of its own; its phrases are written by `phrase_texts` from
    the table's ``seed``, so a popularity rank is one text in every
    partition."""
    from concurrent.futures import ThreadPoolExecutor

    per = -(-rows // partitions)
    sizes = [min(per, rows - i * per) for i in range(partitions)]
    with ThreadPoolExecutor(partitions) as pool:
        return list(pool.map(
            lambda i: _hits(sizes[i], np.random.default_rng([seed, i]), seed),
            range(partitions)))


def _hits(n: int, rng, seed: int):
    """``n`` rows of `hits` drawn from ``rng``; ``seed`` writes the
    phrases' texts."""
    import pyarrow as pa


    event_day = rng.integers(0, _DAYS, n)
    event_date = (_EPOCH_2013_07_01 + event_day).astype(np.int32)
    event_time = (
        _SECS_2013_07_01 + event_day * 86400 + rng.integers(0, 86400, n)
    ).astype(np.int64)
    url_hosts = np.asarray(_URL_HOSTS, dtype=object)
    url_vocab = np.char.add(
        np.repeat(np.char.add(url_hosts.astype(str), "/"), _URL_PATHS),
        np.tile(np.arange(_URL_PATHS).astype(str), len(_URL_HOSTS)))
    url_code = (rng.integers(0, len(_URL_HOSTS), n) * _URL_PATHS
                + rng.integers(0, _URL_PATHS, n))

    # the phrases drawn, ranked by popularity; 0 is the empty string
    ranks = search_phrase_codes(n, rng)
    present = np.flatnonzero(np.bincount(ranks, minlength=1))
    dense = np.zeros(present[-1] + 1, dtype=np.int32)
    dense[present] = np.arange(len(present), dtype=np.int32)
    phrases = phrase_texts(present[present > 0], seed)
    phrase_vocab = pa.concat_arrays(
        [pa.array([""] * int(present[0] == 0), type=pa.string()), phrases])

    cols = {
        "WatchID": rng.integers(1, 2**31 - 1, n).astype(np.int64),
        "UserID": rng.integers(1, 200_000, n).astype(np.int64),
        "CounterID": rng.integers(1, 100, n).astype(np.int32),
        "ClientIP": rng.integers(0, 2**31 - 1, n).astype(np.int32),
        "RegionID": rng.integers(1, 300, n).astype(np.int32),
        "EventDate": event_date.astype("datetime64[D]"),
        "EventTime": event_time,
        "Title": _from_vocabulary(
            _TITLES, rng.integers(0, len(_TITLES), n)),
        "URL": _from_vocabulary(url_vocab, url_code),
        "Referer": _from_vocabulary(
            _REFERERS, rng.choice(len(_REFERERS), n, p=_REFERER_P)),
        # a fixed hash of the URL (its vocabulary code), below 2^31
        "URLHash": (_mix(url_code) >> np.uint64(33)).astype(np.int64),
        "RefererHash": rng.integers(0, 2**31 - 1, n).astype(np.int64),
        "SearchPhrase": _from_vocabulary(phrase_vocab, dense[ranks]),
        "SearchEngineID": np.where(
            rng.random(n) < 0.8, 0, rng.integers(1, 6, n)).astype(np.int16),
        "AdvEngineID": np.where(
            rng.random(n) < 0.95, 0, rng.integers(1, 20, n)).astype(np.int16),
        "MobilePhone": np.where(
            rng.random(n) < 0.85, 0, rng.integers(1, 8, n)).astype(np.int16),
        "MobilePhoneModel": _from_vocabulary(
            _PHONE_MODELS,
            rng.choice(len(_PHONE_MODELS), n, p=_PHONE_MODEL_P)),
        "ResolutionWidth": rng.choice(
            [0, 1024, 1280, 1366, 1440, 1600, 1920, 2560],
            n, p=[0.05, 0.1, 0.2, 0.25, 0.1, 0.1, 0.15, 0.05]
        ).astype(np.int16),
        "WindowClientWidth": rng.integers(0, 2000, n).astype(np.int16),
        "WindowClientHeight": rng.integers(0, 1200, n).astype(np.int16),
        "TraficSourceID": rng.integers(-1, 10, n).astype(np.int8),
        "IsRefresh": (rng.random(n) < 0.1).astype(np.int16),
        "IsLink": (rng.random(n) < 0.2).astype(np.int16),
        "IsDownload": (rng.random(n) < 0.05).astype(np.int16),
        "DontCountHits": (rng.random(n) < 0.05).astype(np.int16),
    }
    return pa.table(cols)


def register_clickbench(ctx, rows: int = 100_000, seed: int = 0):
    t = gen_clickbench(rows=rows, seed=seed)
    ctx.register_arrow("hits", t)
    return t
