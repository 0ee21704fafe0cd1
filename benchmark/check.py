"""The comparison that decides `correct`.

An answer is an Arrow table.  Against the oracle's table it has to hold:
the same number of rows, the same key tuples (in order where the query
orders its answer, as a set where it does not), integer and string
columns equal, and float columns within REL_TOL of the oracle's value.

REL_TOL: float64 on this chip is a float32 pair, which is off by at most
7e-15 per value against IEEE double (PERF.md findings); money held or
summed in float32 is off by 3e-8 and more.  1e-9 lies between the two
with room on both sides; the readings are in PERF.md.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np
import pyarrow as pa

REL_TOL = 1e-9
LIMITS = {"row_count_diff": 0, "key_mismatches": 0,
          "exact_value_mismatches": 0, "float_max_rel_err": REL_TOL}


def _frame(t: pa.Table, keys: List[str], ordered: bool):
    df = t.to_pandas()
    if not ordered:
        df = df.sort_values(keys, na_position="first", kind="stable")
    return df.reset_index(drop=True)


def compare(got: pa.Table, want: pa.Table, keys: List[str],
            ordered: bool) -> Dict[str, float]:
    """The numbers compared, by name; `verdict` holds them to LIMITS."""
    nums = {"row_count_diff": abs(got.num_rows - want.num_rows),
            "key_mismatches": 0, "exact_value_mismatches": 0,
            "float_max_rel_err": 0.0}
    if got.num_columns != want.num_columns:
        nums["exact_value_mismatches"] = max(got.num_rows, 1)
        return nums
    g, w = _frame(got, keys, ordered), _frame(want, keys, ordered)
    g.columns = w.columns  # answers bind by position, as Spark's do
    n = min(len(g), len(w))
    g, w = g.iloc[:n], w.iloc[:n]
    for col in w.columns:
        a, b = g[col], w[col]
        both_null = a.isna().to_numpy() & b.isna().to_numpy()
        if col not in keys and b.dtype.kind == "f":
            av = a.to_numpy(dtype=np.float64, na_value=np.nan)
            bv = b.to_numpy(dtype=np.float64, na_value=np.nan)
            null_diff = np.isnan(av) != np.isnan(bv)
            nums["exact_value_mismatches"] += int(null_diff.sum())
            ok = ~np.isnan(av) & ~np.isnan(bv)
            if ok.any():
                rel = np.abs(av[ok] - bv[ok]) / np.maximum(np.abs(bv[ok]),
                                                           1e-300)
                nums["float_max_rel_err"] = max(nums["float_max_rel_err"],
                                                float(rel.max()))
            continue
        if a.dtype.kind == "f" or b.dtype.kind == "f":
            # nullable integer keys come back from pandas as floats
            av = a.to_numpy(dtype=np.float64, na_value=np.nan)
            bv = b.to_numpy(dtype=np.float64, na_value=np.nan)
            differs = ~((av == bv) | both_null)
        else:
            differs = ~((a.to_numpy() == b.to_numpy()) | both_null)
        which = "key_mismatches" if col in keys else "exact_value_mismatches"
        nums[which] += int(np.asarray(differs, bool).sum())
    return nums


def verdict(nums: Dict[str, float]) -> Tuple[bool, str]:
    """(holds, one line with each number beside its limit)."""
    parts, ok = [], True
    for name, limit in LIMITS.items():
        v = nums[name]
        good = v <= limit
        ok = ok and good
        parts.append(f"{name}={v!r} (limit {limit!r})"
                     + ("" if good else " EXCEEDED"))
    return ok, "; ".join(parts)
