"""`tpcds_data.py`'s store sales beside an item hierarchy and store ids.

TPC-DS q67 rolls a year's store sales up by (category, class, brand,
product name, year, quarter, month, store id) and ranks every rolled-up row
inside its category.  `tpcds_data.py`'s `item` has no product name, draws
category, class and brand independently (no hierarchy to roll up) and its
`store` has no `s_store_id`; its `date_dim` has no `d_month_seq`.  The
tables in place may not change under the cells that run on them, so this is
a generator of its own, named by its own configuration.

  store_sales  `tpcds_data.py`'s, row for row for the same `data_seed` (what
               `tpcds-sf1-x1` draws).
  date_dim     `tpcds_web.py`'s, row for row: `d_month_seq` 1200..1211 is
               the generator's year 2000, a fifth of the sales.
  item         `rows("item")` rows (18,000 at scale 1, dsdgen's count).
               dsdgen's hierarchy: a brand lies in ONE class, a class in ONE
               category: CATEGORIES (10) x CLASSES_PER_CATEGORY (10) x
               BRANDS_PER_CLASS (7) = 700 brands, an item's brand drawn
               uniformly; `i_product_name` is dsdgen's: the item number
               spelled digit by digit in its ten syllables, so 18,000
               distinct names of 3 to 25 bytes.
  store        12 rows with `s_store_id`, 16 characters as dsdgen's
               business keys are, one a store.

No string is NULL: the rollup's NULLs are the Expand's alone (a test draws
its own NULLs).  The interface and the meaning of the two seeds are
`tpcds_data.py`'s: `data_seed` draws every value, `--seed` reorders rows
inside 1,024-row blocks of each file.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

from benchmark.data.tpcds_data import (  # noqa: F401  the same interface
    GENERATORS, reorder, write_parquet_splits)
from benchmark.data import tpcds_data, tpcds_web

CATEGORIES = ("Books", "Children", "Electronics", "Home", "Jewelry", "Men",
              "Music", "Shoes", "Sports", "Women")
CLASSES_PER_CATEGORY = 10
BRANDS_PER_CLASS = 7
CLASS_WORDS = ("accessories", "athletic", "classical", "fiction", "fragrances",
               "infants", "kids", "mens", "pop", "womens")
BRAND_WORDS = ("amalg", "edu pack", "export", "import", "scholar", "corp",
               "brand", "univ", "max", "nameless")
# dsdgen spells an item number with these, one a decimal digit
SYLLABLES = ("bar", "ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "n st")
TABLES = ("store_sales", "date_dim", "item", "store")


def rows(name: str, scale: float) -> int:
    return tpcds_data.rows(name, scale)


def product_name(item_sk: int) -> str:
    return "".join(SYLLABLES[int(d)] for d in reversed(str(item_sk)))


def gen_item(scale: float, seed: int) -> pa.Table:
    n = rows("item", scale)
    rng = np.random.default_rng([int(seed), 67])
    nclass = len(CATEGORIES) * CLASSES_PER_CATEGORY
    brand = rng.integers(0, nclass * BRANDS_PER_CLASS, n)
    cls = brand // BRANDS_PER_CLASS
    cat = cls // CLASSES_PER_CATEGORY
    cat_names = np.array(CATEGORIES)
    class_names = np.array([
        f"{CLASS_WORDS[c % CLASSES_PER_CATEGORY]} "
        f"{CATEGORIES[c // CLASSES_PER_CATEGORY].lower()}"
        for c in range(nclass)])
    brand_names = np.array([
        f"{BRAND_WORDS[(b // BRANDS_PER_CLASS) % 10]}"
        f"{BRAND_WORDS[b % 10]} #{b + 1}"
        for b in range(nclass * BRANDS_PER_CLASS)])
    sk = np.arange(1, n + 1)
    return pa.table({
        "i_item_sk": pa.array(sk),
        "i_item_id": pa.array(np.char.add("AAAAAAAA", np.char.zfill(
            sk.astype(str), 8))),
        "i_category_id": pa.array((cat + 1).astype(np.int32)),
        "i_category": pa.array(cat_names[cat]),
        "i_class_id": pa.array((cls + 1).astype(np.int32)),
        "i_class": pa.array(class_names[cls]),
        "i_brand_id": pa.array((brand + 1).astype(np.int32)),
        "i_brand": pa.array(brand_names[brand]),
        "i_product_name": pa.array([product_name(int(i)) for i in sk]),
        "i_manager_id": pa.array(rng.integers(1, 100, n).astype(np.int32)),
        "i_current_price": pa.array(np.round(rng.random(n) * 100, 2)),
    })


def gen_store(scale: float, seed: int) -> pa.Table:
    t = tpcds_data.gen_store(scale, seed)
    sk = np.arange(1, t.num_rows + 1)
    return t.append_column("s_store_id", pa.array(
        np.char.add("AAAAAAAA", np.char.zfill(sk.astype(str), 8))))


_OWN = {"item": gen_item, "store": gen_store,
        "date_dim": tpcds_web.gen_date_dim}


def make_tables(names, scale: float, data_seed: int, splits: int,
                seed: int) -> dict:
    unknown = set(names) - set(TABLES)
    if unknown:
        raise KeyError(f"tpcds_rollup makes {TABLES}, not {sorted(unknown)}")
    return {n: reorder((_OWN.get(n) or GENERATORS[n])(scale, data_seed),
                       splits, seed)
            for n in names}
