"""A query log shaped like MSLR-WEB30K (Qin & Liu 2013, arXiv 1306.2597),
made from a seed: judged documents of web-search queries, 136 numerical
features a document, a relevance grade 0 to 4, and the query's id.

`make_table(rows, features, seed)` keeps `harness/datagen.py`
`make_table`'s contract: x float32 [features, rows] (column-major), y
[rows], the same arguments give the same bytes, blocks of rows on a few
threads. `features` is 137 at the published shape: 136 feature rows and,
LAST, the query id of each document as float32 (whole numbers under
2^24, so exact), which the configuration names as `ranking_group`; y is
the grade as float32. Queries are consecutive rows, ids ascending, as
the dataset's files have them.

What the source does not give, and this file sets (the configuration's
`assumed` says the same):

  query sizes   long-tailed: round(lognormal(mu 4.54, sigma 0.70))
                clipped to 1 .. 1,251, which is a mean of about 120
                (WEB30K: 119.6) and a longest query of 1,251 (WEB30K's);
                every table has at least one query of 1,251 documents
                (of a quarter of the rows, if that is fewer) and one in
                2,000 queries, at least three, of a single document
  features      standard normal, then 1 % NaN in columns 1 and 9, put in
                after the grades so that none leaks into them
  grades        cuts of a latent score at the normal quantiles of the
                shares 51, 33, 13, 2 and 1 % (about WEB30K's). The
                latent has unit variance: 0.55 of it linear in the
                first twelve columns with fixed falling weights, 0.10 a
                product of columns 12 and 13, 0.10 an offset of the
                query (some queries have many relevant documents, many
                have none in their top grades), 0.25 noise. A ranker
                can learn 0.65 of it; the query's offset moves no
                ranking within a query
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 20
NAN_COLUMNS = (1, 9)
NAN_SHARE = 0.01
THREADS = 4
LONGEST = 1251
SIZE_MU, SIZE_SIGMA = 4.54, 0.70
SINGLE_SHARE = 1 / 2000
LINEAR_WEIGHTS = (1.0, 0.9, 0.8, 0.7, 0.6, 0.55, 0.5, 0.45, 0.4, 0.35, 0.3,
                  0.25)
SHARE_LINEAR, SHARE_PRODUCT, SHARE_QUERY, SHARE_NOISE = 0.55, 0.10, 0.10, 0.25
# The standard normal's quantiles at 0.51, 0.84, 0.97 and 0.99.
GRADE_CUTS = (0.02506891, 0.99445788, 1.88079361, 2.32634787)


def query_sizes(rows: int, seed: int) -> np.ndarray:
    """The documents of each query, summing to `rows`."""
    rng = np.random.default_rng([0x51E5, seed])
    draw = max(rows // 50, 64)  # more than enough: the mean is about 120
    sizes = np.clip(np.rint(rng.lognormal(SIZE_MU, SIZE_SIGMA, draw)), 1,
                    LONGEST).astype(np.int64)
    # The tail and the single documents every table has, at seeded places
    # among the queries that are sure to be kept.
    sure = max(rows // 300, 5)
    singles = max(3, int(rows / 120 * SINGLE_SHARE))
    places = rng.permutation(sure)[:singles + 1]
    sizes[places[0]] = min(LONGEST, max(rows // 4, 1))
    sizes[places[1:]] = 1
    count = int(np.searchsorted(np.cumsum(sizes), rows)) + 1
    sizes = sizes[:count]
    sizes[-1] = rows - sizes[:-1].sum()  # the last query: what is left
    return sizes


def _fill_block(seed, block, x, y, query_of_row, query_offset) -> None:
    lo = block * BLOCK_ROWS
    hi = min(lo + BLOCK_ROWS, x.shape[1])
    rng = np.random.default_rng([block, seed])
    xb = rng.standard_normal((x.shape[0] - 1, hi - lo), dtype=np.float32)
    w = np.asarray(LINEAR_WEIGHTS, np.float32)
    linear = np.tensordot(w / np.sqrt(np.sum(w * w)), xb[:len(w)], axes=1)
    latent = (np.float32(np.sqrt(SHARE_LINEAR)) * linear
              + np.float32(np.sqrt(SHARE_PRODUCT)) * xb[12] * xb[13]
              + np.float32(np.sqrt(SHARE_QUERY))
              * query_offset[query_of_row[lo:hi]]
              + np.float32(np.sqrt(SHARE_NOISE))
              * rng.standard_normal(hi - lo, dtype=np.float32))
    y[lo:hi] = np.searchsorted(np.asarray(GRADE_CUTS, np.float32), latent)
    for col in NAN_COLUMNS:
        xb[col, rng.random(hi - lo) < NAN_SHARE] = np.nan
    x[:-1, lo:hi] = xb
    x[-1, lo:hi] = query_of_row[lo:hi]


def make_table(rows: int, features: int, seed: int):
    """Returns (x, y): x float32 [features, rows], its last row the query
    id; y float32 [rows], the grade 0 to 4. `seed` is any whole number;
    its absolute value is used."""
    if features < 15:
        raise ValueError("the grades need 14 feature columns and the id row")
    seed = abs(int(seed))
    sizes = query_sizes(rows, seed)
    if len(sizes) >= 1 << 24:
        raise ValueError("a float32 id row holds fewer than 2^24 queries")
    query_of_row = np.repeat(np.arange(len(sizes), dtype=np.int32), sizes)
    query_offset = np.random.default_rng([0x0FF5, seed]).standard_normal(
        len(sizes), dtype=np.float32)
    x = np.empty((features, rows), np.float32)
    y = np.empty((rows,), np.float32)
    blocks = range((rows + BLOCK_ROWS - 1) // BLOCK_ROWS)
    with ThreadPoolExecutor(THREADS) as pool:
        for f in [pool.submit(_fill_block, seed, b, x, y, query_of_row,
                              query_offset) for b in blocks]:
            f.result()
    return x, y
