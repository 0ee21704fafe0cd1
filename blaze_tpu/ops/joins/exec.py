"""Equi-joins: sort-merge / shuffled-hash / broadcast, all join types.

Parity: sort_merge_join_exec.rs:397 + joins/smj/{full,semi,existence}_join.rs,
joins/join_hash_map.rs:277 JoinHashMap, broadcast_join_exec.rs:695 (SHJ and
BHJ share probe code), broadcast_join_build_hash_map_exec.rs (build map made
once per broadcast, cached via the resource map).

TPU-first redesign (SURVEY.md §7 step 6): instead of a pointer-chasing hash
map, the build side becomes a HASH-SORTED table: device xxhash64 over the
join keys, device sort by hash.  Probing is vectorized searchsorted over the
sorted hashes (binary search lowers to fused gathers), candidate pairs expand
host-side with numpy (data-dependent sizes live on host, the static-shape
boundary), and every candidate verifies actual key equality — hash collisions
cannot produce wrong results.  All three exec flavors share this probe core,
mirroring how the reference shares probe code between SHJ and BHJ.

Under device placement the common case, an inner join on a unique
fixed-width build key, does all of that in one device program a probe
batch (`kernels/join.probe_gather`): the rows of both sides never leave
the chip, and the host reads back one count (`_probes_on_device`).  A
build side with one dense integer key, which a dimension's surrogate key
is, is addressed by the key itself (`JoinMap.direct_key`).
"""

from __future__ import annotations

import enum
import itertools
import threading
import weakref
from typing import (Dict, Iterator, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from blaze_tpu import config
from dataclasses import replace

from blaze_tpu.batch import (ColumnBatch, DeviceColumn, DictColumn,
                             column_of, one_schema, plain_columns)
from blaze_tpu.bridge import tracing, xla_stats
from blaze_tpu.xputil import asnp, to_device, to_host
from blaze_tpu.bridge.context import current_task
from blaze_tpu.bridge.resource import get_or_create
from blaze_tpu.exprs import PhysicalExpr
from blaze_tpu.kernels import hashing as H
from blaze_tpu.memory import MemConsumer, MemManager
from blaze_tpu.ops.base import BatchIterator, CoalesceStream, ExecutionPlan
from blaze_tpu.schema import BOOL, DataType, Field, Schema, TypeId

# process-unique default broadcast ids (see BroadcastJoinExec.__init__)
_local_bid = itertools.count()


class JoinType(enum.Enum):
    INNER = "inner"
    LEFT = "left"            # left outer
    RIGHT = "right"          # right outer
    FULL = "full"
    LEFT_SEMI = "left_semi"
    LEFT_ANTI = "left_anti"
    RIGHT_SEMI = "right_semi"
    RIGHT_ANTI = "right_anti"
    EXISTENCE = "existence"  # left rows + bool `exists` column


import functools


from blaze_tpu.kernels.hashing import norm_float_keys as _norm_float_keys


@functools.lru_cache(maxsize=128)
def _hash_valid_jit(tids: Tuple[str, ...]):
    """One compiled program per key-type signature: chained xxhash64 +
    any-null mask (eagerly this is ~100 dispatches per batch and
    dominated the probe, like the partitioner before it was jitted)."""
    from blaze_tpu.kernels.join import hash_valid

    def f(flat_cols):
        return hash_valid(flat_cols, tids)
    return xla_stats.meter_jit(f, name="join.hash_valid")


def _device_hash_keys(batch: ColumnBatch, key_exprs: Sequence[PhysicalExpr]
                      ) -> Tuple[np.ndarray, np.ndarray, List[pa.Array]]:
    """(hash int64[num_rows], any_null bool[num_rows], key arrays host).

    Host placement hashes in numpy directly — batches are unpadded there,
    and a jit per distinct batch length would recompile the ~60-op hash
    chain for every tail batch."""
    from blaze_tpu.bridge.placement import host_resident
    n = batch.num_rows
    on_host = host_resident()
    cap = n if on_host else batch.capacity
    flat_cols = []
    tids = []
    key_arrays = []
    for e in key_exprs:
        v = e.evaluate(batch)
        arr = v.to_host(n)
        key_arrays.append(arr)
        if v.is_device and v.dictionary is None:
            data = asnp(v.data)[:cap] if on_host else v.data
            valid = asnp(v.validity)[:cap] if on_host else v.validity
            flat_cols.append((data, valid))
            tids.append(_tid(v.dtype))
        else:
            (mat, lengths), valid = H.string_column_to_padded_bytes(arr)
            # pad rows to capacity (lanes must line up with fixed-width
            # keys) and width to a pow2 bucket (bounded recompiles)
            w = max(4, 1 << (mat.shape[1] - 1).bit_length()) \
                if mat.shape[1] else 4
            full = np.zeros((cap, w), dtype=mat.dtype)
            full[:mat.shape[0], :mat.shape[1]] = mat
            full_len = np.zeros(cap, dtype=lengths.dtype)
            full_len[:len(lengths)] = lengths
            if on_host:
                flat_cols.append(((full, full_len), _pad(valid, cap)))
            else:
                flat_cols.append(to_device(((full, full_len),
                                            _pad(valid, cap))))
            tids.append("utf8")
    if on_host:
        flat_cols = _norm_float_keys(flat_cols, tids, np)
        cols = [(v, val, tid) for (v, val), tid in zip(flat_cols, tids)]
        h_np = np.asarray(H.hash_columns(cols, seed=42, xp=np,
                                         algo="xxhash64"))
        anyn_np = np.zeros(cap, dtype=bool)
        for (_v, val) in flat_cols:
            anyn_np |= ~np.asarray(val)
        return h_np[:n], anyn_np[:n], key_arrays
    h, anyn = _hash_valid_jit(tuple(tids))(flat_cols)
    h_np, anyn_np = to_host((h, anyn))
    return h_np[:n], anyn_np[:n].copy(), key_arrays


def promote_join_key_exprs(lkeys, rkeys, lschema, rschema):
    """Widen mismatched join-key expression pairs to a common numeric
    type (int/int -> int64, numeric mix -> float64) so every join path
    hashes/compares identical types — the murmur/xxhash probe hashes
    int32 and int64 of equal value differently.  Spark's analyzer
    inserts these casts during resolution; hand-built plans may not."""
    from blaze_tpu.exprs.cast import Cast
    from blaze_tpu.schema import FLOAT64, INT64
    out_l, out_r = [], []
    for le, re in zip(lkeys, rkeys):
        lt = le.data_type(lschema)
        rt = re.data_type(rschema)
        if lt.id == rt.id:
            out_l.append(le)
            out_r.append(re)
            continue
        if lt.is_integer and rt.is_integer:
            common = INT64
        elif ((lt.is_integer or lt.is_floating) and
              (rt.is_integer or rt.is_floating)):
            common = FLOAT64
        else:
            out_l.append(le)
            out_r.append(re)
            continue
        out_l.append(le if lt.id == common.id else Cast(le, common))
        out_r.append(re if rt.id == common.id else Cast(re, common))
    return out_l, out_r


def _pad(v: np.ndarray, n: int) -> np.ndarray:
    if len(v) == n:
        return v
    out = np.zeros(n, dtype=v.dtype)
    out[:len(v)] = v
    return out


def _tid(dtype) -> str:
    return dtype.id.value


class _Resident(NamedTuple):
    """What `_DeviceBuild` holds on its chip."""
    uh: jax.Array
    ustart: jax.Array
    ucount: jax.Array
    # of a `unique_fixed` map only
    urow: Optional[jax.Array] = None
    keys: Optional[Tuple[jax.Array, ...]] = None
    cols: Optional[Tuple[Tuple[jax.Array, jax.Array], ...]] = None
    dtypes: Optional[Tuple[DataType, ...]] = None
    # a build column's dictionary where it is utf8 (its lane holds int32
    # codes under it), else None
    dicts: Optional[Tuple[Optional[pa.Array], ...]] = None
    # of a map with a `direct_key` only: (drow, kmin)
    direct: Optional[Tuple[jax.Array, jax.Array]] = None


class _DeviceBuild(MemConsumer):
    """One chip's copy of a `JoinMap`'s build side, placed once and kept
    while the map lives: the hash index of every probe (`uh`, `ustart`,
    `ucount`) and, where the map is `unique_fixed`, what
    `kernels/join.probe_gather` reads: `urow`, the build row of each
    distinct hash (-1 for a row with a NULL key), the key columns' data
    and the build columns (a utf8 column as the int32 codes of
    `JoinMap.coded_table`, its sorted dictionary kept beside them).  Index arrays are padded to 2^k - 1 entries
    (the largest int64, counts of 0: nothing matches padding, and the
    search takes as many rounds as over the entries alone) and the rows
    to their capacity bucket, so a new build size is rarely a new
    program.  Where the map has a `direct_key`, also the direct-address
    index `probe_gather` reads in place of the search: `drow`, int32,
    the key range padded to a power of two, drow[k - kmin] the build row
    of key k and -1 where there is none, and `kmin` in the key's type.
    Charged to the chip of the task that places it.  Shed, it
    lets go (`held` None: a probe under way keeps what it was handed) and
    the next probe on that chip places the copy again."""

    def __init__(self):
        super().__init__("join_build")
        self.held: Optional[_Resident] = None

    def place(self, jmap: "JoinMap") -> _Resident:
        n = len(jmap.uh)
        size = (1 << n.bit_length()) - 1

        def padded(a: np.ndarray, fill) -> np.ndarray:
            out = np.full(size, fill, dtype=a.dtype)
            out[:n] = a
            return out

        index = [padded(jmap.uh, np.iinfo(np.int64).max),
                 padded(jmap.ustart, 0), padded(jmap.ucount, 0)]
        if jmap.unique_fixed:
            row = jmap.sorted_idx[jmap.ustart]
            index.append(padded(
                np.where(jmap._valid[row], row, -1).astype(np.int32), -1))
        nbytes = sum(a.nbytes for a in index)
        held = _Resident(*to_device(index))
        if jmap.direct_key is not None:
            kmin, span = jmap.direct_key
            live = np.flatnonzero(jmap._valid)
            drow = np.full(1 << (span - 1).bit_length(), -1, dtype=np.int32)
            drow[jmap._int_keys()[live] - kmin] = live
            nbytes += drow.nbytes
            held = held._replace(direct=to_device((drow, kmin)))
        if jmap.unique_fixed:
            rows = ColumnBatch.from_arrow(jmap.coded_table)
            keys = tuple(e.evaluate(rows).to_device(rows.capacity).data
                         for e in jmap._key_exprs)
            nbytes += rows.nbytes_device() + sum(k.nbytes for k in keys)
            held = held._replace(
                keys=keys,
                cols=tuple((c.data, c.validity) for c in rows.columns),
                dtypes=tuple(c.dtype for c in rows.columns),
                dicts=tuple(getattr(c, "dictionary", None)
                            for c in rows.columns))
        self.held = held
        self.set_spillable(MemManager.get())
        self.update_mem_used(nbytes)
        return held

    def spill(self) -> int:
        self.held = None
        released, self._mem_used = self._mem_used, 0
        return released


def key_ranges(rows: int, key_arrays) -> Optional[List[Optional[Tuple]]]:
    """A key position: the (min, max) Arrow scalars of a build side's
    values of that key over its non-NULL rows where the key is of an
    integer or date type, else None.  The list is None where some key has
    no value at all (an empty build side, an all-NULL key): no probe row
    can match then.  The ONE derivation of the join-key runtime filter's
    ranges: the row filter of the Acero lane's collection and the
    row-group pruning of every lane's probe scan read it."""
    if not rows or any(k.null_count == len(k) for k in key_arrays):
        return None
    ranges = []
    for key in key_arrays:
        if not (pa.types.is_integer(key.type) or pa.types.is_date(key.type)):
            ranges.append(None)
            continue
        mm = pc.min_max(key)
        ranges.append((mm["min"], mm["max"]))
    return ranges


class JoinMap:
    """Hash-sorted build table (the JoinHashMap analog, join_hash_map.rs:277).

    Probe lookups run through one of three vectorized paths:
      * device-resident (accelerator placement, an inner join on a
        unique fixed-width key: `BaseJoinExec._probes_on_device`):
        kernels/join.py `probe_gather`, one program a probe batch, the
        rows never leave the chip; where the build side has ONE dense
        integer key (`direct_key`) the program reads the build row at
        `key - min`, with no hash and no search;
      * device (accelerator placement, every other join): kernels/join.py
        jit'd binary search + scan-based bounded pair expansion, one
        scalar sync per batch, pairs verified and rows taken on the host;
      * host placement: Arrow's C++ hash table (pc.index_in) over the
        unique build hashes + run-length expansion in numpy.
    Both device paths read this chip's resident copy (`on_device`).
    """

    def __init__(self, table: pa.Table, key_exprs: Sequence[PhysicalExpr],
                 schema: Schema):
        # as it was collected: a utf8 column as plain strings or, where
        # the build side's batches carried dictionary columns, as an Arrow
        # dictionary array (nothing was decoded to collect it)
        self._collected = table.combine_chunks()
        self.schema = schema
        self._key_exprs = list(key_exprs)
        self._built = False
        self.matched = np.zeros(self._collected.num_rows, dtype=bool)
        self._on_device: Dict[int, _DeviceBuild] = {}
        self._on_device_lock = threading.Lock()

    @functools.cached_property
    def table(self) -> pa.Table:
        """The build side as plain Arrow columns, for the paths that take
        rows from it on the host (the pair expansion, Acero): a column
        collected as codes is decoded here, once a map, and only where
        such a path asks."""
        t = self._collected
        coded = sum(pa.types.is_dictionary(f.type) for f in t.schema)
        if not coded:
            return t
        xla_stats.note_dict(dict_rows_decoded=t.num_rows * coded)
        tracing.instant("dict_decode", rows=t.num_rows * coded)
        return pa.Table.from_arrays(plain_columns(t.columns),
                                    names=t.schema.names)

    def _ensure_index(self) -> None:
        """Hash-sort the build side on first probe.  Lazy because the
        Acero host path and the null-aware-anti empty-probe cases never
        touch the hash index at all."""
        if self._built:
            return
        with tracing.span("join_build", rows=self.num_rows, step="index"):
            self._build_index()
        self._built = True

    def _build_index(self) -> None:
        from blaze_tpu.kernels.join import build_runs
        n = self.num_rows
        if n:
            # (a string KEY hashes as the string it is; a fixed-width key
            # does not care how the payload is held)
            cb = ColumnBatch.from_arrow(
                self.coded_table if all(
                    e.data_type(self.schema).is_fixed_width
                    for e in self._key_exprs) else self.table)
            hashes, any_null, self.key_arrays = _device_hash_keys(
                cb, self._key_exprs)
            # null keys never match: a reserved hash bucket we skip
            self._valid = ~any_null
            order = np.argsort(hashes, kind="stable")
            self.sorted_hashes = hashes[order]
            # slot arrays narrow to i32 below 2^31 build rows — keeps
            # the probe's gather indices off TPU 64-bit emulation
            self.sorted_idx = (order.astype(np.int32)
                               if n < (1 << 31) else order)
            self.uh, self.ustart, self.ucount = build_runs(self.sorted_hashes)
            self._uh_pa = pa.array(self.uh, type=pa.int64())
        else:
            self._valid = np.zeros(0, dtype=bool)
            self.sorted_hashes = np.zeros(0, dtype=np.int64)
            self.sorted_idx = np.zeros(0, dtype=np.int32)
            self.uh = np.zeros(0, dtype=np.int64)
            self.ustart = np.zeros(0, dtype=np.int32)
            self.ucount = np.zeros(0, dtype=np.int32)
            self.key_arrays = []

    @property
    def num_rows(self) -> int:
        return self._collected.num_rows

    @property
    def has_null_keys(self) -> bool:
        self._ensure_index()
        return bool((~self._valid).any())

    @functools.cached_property
    def key_tids(self) -> Tuple[str, ...]:
        return tuple(_tid(e.data_type(self.schema))
                     for e in self._key_exprs)

    @functools.cached_property
    def coded_table(self) -> pa.Table:
        """The build side with every utf8 column dictionary-encoded against
        the SORTED dictionary of its values: built once a map, where the
        table was collected, so code order is string order and every task
        that probes the map hands on the same dictionaries."""
        from blaze_tpu.batch import encode_sorted
        t = self._collected

        def utf8(f):
            return pa.types.is_string(f.type) or pa.types.is_dictionary(
                f.type)

        if not any(utf8(f) for f in t.schema):
            return t
        with tracing.span("join_build", rows=t.num_rows,
                          step="dictionaries"):
            return pa.Table.from_arrays(
                [encode_sorted(col) if utf8(f) else col
                 for f, col in zip(t.schema, t.columns)],
                names=t.schema.names)

    @functools.cached_property
    def unique_fixed(self) -> bool:
        """No two build rows share a hash, so a probe row has at most one
        candidate, the build side's keys are fixed-width and its columns
        fixed-width or utf8 (a code lane on the chip): the build side
        `probe_gather` takes."""
        self._ensure_index()
        return (all(f.data_type.is_fixed_width
                    or f.data_type.id == TypeId.UTF8 for f in self.schema)
                and all(e.data_type(self.schema).is_fixed_width
                        for e in self._key_exprs)
                and (not len(self.ucount) or int(self.ucount.max()) == 1))

    @functools.cached_property
    def key_ranges(self):
        """`key_ranges` of the build side's keys (the streamed lanes'; the
        Acero lane reads its own key columns and never builds the index)."""
        self._ensure_index()
        return key_ranges(self.num_rows, self.key_arrays)

    def _int_keys(self) -> np.ndarray:
        """The one integer key's value a build row, as int64 (a date or
        a timestamp as the integer it is stored as; 0 where the key is
        NULL)."""
        key = self.key_arrays[0]
        if not pa.types.is_integer(key.type):
            key = key.view(pa.int32() if key.type.bit_width == 32
                           else pa.int64())
        return key.fill_null(0).to_numpy(zero_copy_only=False) \
            .astype(np.int64)

    @functools.cached_property
    def direct_key(self) -> Optional[Tuple[np.generic, int]]:
        """(smallest valid build key, in the key's own type; entries of
        the key range) where `probe_gather` can address the build side by
        the key itself, else None.  A surrogate key is dense by
        construction, and a unique dense integer key needs no hash table:
        the build row of key k stands at k - min (Spark's
        `LongToUnsafeRowMap` has the same dense mode).  So: the map is
        `unique_fixed`, has ONE key, of an integer type, and its valid
        keys' range is at most max(8 x build rows, 65,536) and 2^24
        entries (4 B each on the chip).  Decided once a map from what the
        build side itself shows; two keys, a float key, a sparse key set
        and an empty or all-NULL build side keep the hash-sorted index."""
        if len(self._key_exprs) != 1:
            return None
        dtype = self._key_exprs[0].data_type(self.schema)
        if (not dtype.is_integer or not self.unique_fixed
                or not self._valid.any()):
            return None
        keys = self._int_keys()[self._valid]
        kmin = int(keys.min())
        span = int(keys.max()) - kmin + 1   # Python integers: cannot wrap
        if span > min(max(8 * self.num_rows, 1 << 16), 1 << 24):
            return None
        return dtype.np_dtype().type(kmin), span

    def on_device(self) -> _Resident:
        """The copy of the build side on the current task's chip, placed
        by the first task that asks there (the map is shared by every
        task of a stage, and they run on as many chips as there are)."""
        self._ensure_index()
        chip = current_task().device_id
        with self._on_device_lock:
            copy = self._on_device.get(chip)
            held = copy.held if copy is not None else None
            if held is None:
                if copy is not None:
                    copy.unregister()
                copy = self._on_device[chip] = _DeviceBuild()
                held = copy.place(self)
                # the manager keeps a consumer until it is unregistered
                weakref.finalize(self, copy.unregister)
        return held

    def lookup(self, probe_hashes: np.ndarray, probe_null: np.ndarray,
               probe_keys: List[pa.Array]
               ) -> Tuple[np.ndarray, np.ndarray]:
        """Candidate-verified (probe_idx, build_idx) pair arrays."""
        n = len(probe_hashes)
        if self.num_rows == 0 or n == 0:
            return (np.zeros(0, dtype=np.int64),) * 2
        self._ensure_index()
        from blaze_tpu.bridge.placement import host_resident
        if host_resident():
            probe_idx, build_idx = self._lookup_host(probe_hashes,
                                                     probe_null)
        else:
            from blaze_tpu.kernels.join import probe_expand_device
            index = self.on_device()
            ph, pn = to_device((probe_hashes, probe_null))
            probe_idx, build_idx = probe_expand_device(
                index.uh, index.ustart, index.ucount, self.sorted_idx,
                ph, pn)
        if not len(probe_idx):
            return (np.zeros(0, dtype=np.int64),) * 2
        # drop null-key build rows, then verify true equality per key
        # column (NaN == NaN for float keys: Spark join-key semantics)
        keep = self._valid[build_idx]
        for pk, bk in zip(probe_keys, self.key_arrays):
            if not keep.any():
                break
            pe = pk.take(pa.array(probe_idx, type=pa.int64()))
            be = bk.take(pa.array(build_idx, type=pa.int64()))
            eq = pc.equal(pe, be).fill_null(False)
            if pa.types.is_floating(pe.type):
                eq = pc.or_(eq, pc.and_(pc.is_nan(pe), pc.is_nan(be)))
                eq = eq.fill_null(False)
            keep &= np.asarray(eq)
        return probe_idx[keep], build_idx[keep]

    def _lookup_host(self, probe_hashes: np.ndarray, probe_null: np.ndarray
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Arrow C++ hash-table lookup (GIL-releasing) + numpy run
        expansion — replaces two numpy searchsorted passes that measured
        ~3 ms per 72K-row batch each."""
        ui = pc.index_in(pa.array(probe_hashes, type=pa.int64()),
                         value_set=self._uh_pa)
        ui_np = np.asarray(ui.fill_null(len(self.uh)), dtype=np.int64)
        hit = (ui_np < len(self.uh)) & ~probe_null
        lo = np.where(hit, self.ustart[np.minimum(ui_np, len(self.uh) - 1)],
                      0)
        counts = np.where(hit, self.ucount[np.minimum(ui_np,
                                                      len(self.uh) - 1)], 0)
        total = int(counts.sum())
        if total == 0:
            return (np.zeros(0, dtype=np.int64),) * 2
        n = len(probe_hashes)
        probe_idx = np.repeat(np.arange(n, dtype=np.int64), counts)
        starts = np.repeat(lo, counts)
        offs = np.arange(total, dtype=np.int64) - \
            np.repeat(np.cumsum(counts) - counts, counts)
        build_idx = self.sorted_idx[starts + offs]
        return probe_idx, build_idx


def build_join_map(batches: Iterator[pa.RecordBatch], schema: Schema,
                   key_exprs: Sequence[PhysicalExpr]) -> JoinMap:
    blist = list(batches)
    table = (pa.Table.from_batches(one_schema(blist)) if blist
             else pa.Table.from_batches([], schema=schema.to_arrow()))
    return JoinMap(table, key_exprs, schema)


class BaseJoinExec(ExecutionPlan):
    """Shared probe core.  `build_side` names which child is materialized."""

    def __init__(self, left: ExecutionPlan, right: ExecutionPlan,
                 left_keys: Sequence[PhysicalExpr],
                 right_keys: Sequence[PhysicalExpr],
                 join_type: JoinType,
                 build_side: str = "right",
                 join_filter: Optional[PhysicalExpr] = None,
                 existence_col: str = "exists",
                 null_aware_anti: bool = False):
        super().__init__([left, right])
        assert build_side in ("left", "right")
        # widen mismatched key pairs ONCE here so every probe path —
        # Acero one-shot, streaming run cursors, device hash probe —
        # sees identical key types (Spark's analyzer inserts these casts;
        # hand-built plans may not).  The cached broadcast build-map path
        # (BuildHashMapExec) still relies on the upstream cast guarantee:
        # its map is hashed before this node exists.
        self.left_keys, self.right_keys = promote_join_key_exprs(
            list(left_keys), list(right_keys), left.schema, right.schema)
        self.join_type = join_type
        self.build_side = build_side
        self.join_filter = join_filter
        self._existence_col = existence_col
        # NOT IN subquery semantics (ref BroadcastJoinExecNode
        # is_null_aware_anti_join): a NULL anywhere makes membership
        # three-valued UNKNOWN, so null build keys reject everything and
        # null probe keys never pass
        self.null_aware_anti = null_aware_anti
        self._out_schema = self._build_schema()

    # -- schema -------------------------------------------------------------
    def _build_schema(self) -> Schema:
        l, r = self.children[0].schema, self.children[1].schema
        jt = self.join_type
        if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            return l
        if jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            return r
        if jt == JoinType.EXISTENCE:
            return Schema(list(l) + [Field(self._existence_col, BOOL, False)])
        fields = []
        for f in l:
            nullable = f.nullable or jt in (JoinType.RIGHT, JoinType.FULL)
            fields.append(Field(f.name, f.data_type, nullable))
        for f in r:
            nullable = f.nullable or jt in (JoinType.LEFT, JoinType.FULL)
            fields.append(Field(f.name, f.data_type, nullable))
        return Schema(fields)

    @property
    def schema(self) -> Schema:
        return self._out_schema

    @property
    def num_partitions(self) -> int:
        probe = 0 if self.build_side == "right" else 1
        return self.children[probe].num_partitions

    # -- build-side acquisition (overridden by BroadcastJoinExec) ----------
    def _get_join_map(self, partition: int) -> JoinMap:
        build = 1 if self.build_side == "right" else 0
        child = self.children[build]
        stream = (b.compact().to_arrow(keep_dict=True)
                  for b in child.execute(partition))
        keys = self.right_keys if build == 1 else self.left_keys
        with tracing.span("join_build", partition=partition,
                          step="collect"):
            return build_join_map(stream, child.schema, keys)

    # -- execution ----------------------------------------------------------
    def execute(self, partition: int) -> BatchIterator:
        return self._probe_with_map(self._get_join_map(partition),
                                    partition)

    def _probe_with_map(self, jmap: "JoinMap", partition: int
                        ) -> BatchIterator:
        probe_is_left = self.build_side == "right"
        probe = self.children[0 if probe_is_left else 1]
        probe_keys = self.left_keys if probe_is_left else self.right_keys

        from blaze_tpu.bridge.placement import host_resident
        if host_resident() and self._pa_join_eligible():
            # host placement: Arrow's C++ hash join (Acero, GIL-releasing,
            # all cores) is the host-engine analog of the reference's
            # native probe (join_hash_map.rs:277); the jit'd probe kernels
            # (kernels/join.py) stay the device path
            return iter(CoalesceStream(
                self._pa_join(jmap, partition, probe, probe_keys,
                              probe_is_left),
                metrics=self.metrics))
        conjuncts = (self._key_range_conjuncts(jmap.key_ranges, probe.schema,
                                               probe_keys)
                     if self._runtime_filter_on(probe_is_left) else [])
        return iter(CoalesceStream(
            self._stream_probe(jmap,
                               probe.execute_pruned(partition, conjuncts),
                               probe_keys, probe_is_left),
            metrics=self.metrics))

    def _stream_probe(self, jmap, batches, probe_keys, probe_is_left):
        """Incremental vectorized probe: the build index is hashed once,
        batches stream through lookup (bounded memory)."""
        eligible = self._probes_on_device(jmap, probe_keys, probe_is_left)
        direct = eligible and jmap.direct_key is not None
        chip = current_task().device_id
        for batch in batches:
            if batch.num_rows == 0:
                continue
            # a utf8 probe column has a device form only as codes
            on_device = eligible and all(
                isinstance(c, DeviceColumn) for c in batch.columns)
            xla_stats.note_join_probe(chip, on_device, batch.num_rows,
                                      direct and on_device)
            if on_device:
                with tracing.span("join_probe", rows=batch.num_rows,
                                  lane="device",
                                  index="direct" if direct else "search"):
                    out = self._probe_batch_device(jmap, batch, probe_keys,
                                                   probe_is_left)
                if out is not None:
                    yield out
                continue
            batch = batch.compact()
            if batch.num_rows == 0:
                continue
            yield from self._probe_batch(jmap, batch, probe_keys,
                                         probe_is_left)
        yield from self._emit_unmatched_build(jmap, probe_is_left)

    def device_probe_planned(self) -> bool:
        """What the PLAN says of `_probes_on_device`, before any build
        side exists: an inner join with no filter whose keys are
        fixed-width and of one type a pair and whose columns, both sides,
        are fixed-width or utf8.  Whether a probe row has at most one
        candidate is the build side's to say, when it is collected."""
        sides = (self.children[0].schema, self.children[1].schema)
        keys = (self.left_keys, self.right_keys)
        tids = [tuple(_tid(e.data_type(s)) for e in k)
                for k, s in zip(keys, sides)]
        return (self.join_type == JoinType.INNER
                and self.join_filter is None and tids[0] == tids[1]
                and all(e.data_type(s).is_fixed_width
                        for k, s in zip(keys, sides) for e in k)
                and all(f.data_type.is_fixed_width
                        or f.data_type.id == TypeId.UTF8
                        for s in sides for f in s))

    def _probes_on_device(self, jmap: JoinMap,
                          probe_keys: Sequence[PhysicalExpr],
                          probe_is_left: bool) -> bool:
        """Whether this join's probe batches stay on the chip
        (`_probe_batch_device`): batches live there, the join is inner
        with no filter, a probe row has at most one candidate, every key
        of both sides is fixed-width, the keys of one type a pair, and
        every column fixed-width or utf8 (a build column as its code
        lane; a probe batch's has to arrive as a `DictColumn`, which
        `_stream_probe` looks at a batch).  Decided once a join, from what
        the plan and the build side say; everything else goes through
        `_probe_batch`."""
        from blaze_tpu.bridge.placement import host_resident
        if (host_resident() or self.join_type != JoinType.INNER
                or self.join_filter is not None):
            return False
        probe_schema = self.children[0 if probe_is_left else 1].schema
        return (all(f.data_type.is_fixed_width
                    or f.data_type.id == TypeId.UTF8 for f in probe_schema)
                and tuple(_tid(e.data_type(probe_schema))
                          for e in probe_keys) == jmap.key_tids
                and jmap.unique_fixed)

    def _probe_batch_device(self, jmap: JoinMap, batch: ColumnBatch,
                            probe_keys: Sequence[PhysicalExpr],
                            probe_is_left: bool) -> Optional[ColumnBatch]:
        """One probe batch through `kernels/join.probe_gather`: in on the
        device (selection and all), out on the device, the matched rows of
        both sides packed to the front; the host reads their count.  The
        program reads whichever index the resident copy holds: the
        direct-address one of a `direct_key`, else the hash-sorted one."""
        from blaze_tpu.kernels.join import probe_gather
        if jmap.num_rows == 0:
            return None
        build = jmap.on_device()
        keys = [e.evaluate(batch).to_device(batch.capacity)
                for e in probe_keys]
        index = ((None, None, None) if build.direct is not None
                 else (build.uh, build.urow, build.keys))
        probe_cols, build_cols, count = probe_gather(
            *index, build.cols,
            tuple((k.data, k.validity) for k in keys),
            tuple((c.data, c.validity) for c in batch.columns),
            np.int32(batch.num_rows), batch.selection,
            tids=jmap.key_tids, direct=build.direct)
        n = int(to_host(count))
        if n == 0:
            return None
        # (`replace` keeps a probe column's class: a dictionary column
        # stays one, under its dictionary)
        probe_out = [replace(c, data=d, validity=v)
                     for c, (d, v) in zip(batch.columns, probe_cols)]
        build_out = [column_of(t, d, v, codes)
                     for t, (d, v), codes in zip(build.dtypes, build_cols,
                                                 build.dicts)]
        coded = sum(isinstance(c, DictColumn) for c in probe_out + build_out)
        if coded:
            xla_stats.note_dict(chip=current_task().device_id,
                                dict_rows_coded=n * coded)
        return ColumnBatch(
            self.schema,
            probe_out + build_out if probe_is_left
            else build_out + probe_out, n)

    # -- host placement: Arrow C++ (Acero) hash join -----------------------
    _PA_JOIN_TYPES = {
        JoinType.INNER: "inner",
        JoinType.LEFT: "left outer",
        JoinType.RIGHT: "right outer",
        JoinType.FULL: "full outer",
        JoinType.LEFT_SEMI: "left semi",
        JoinType.LEFT_ANTI: "left anti",
        JoinType.RIGHT_SEMI: "right semi",
        JoinType.RIGHT_ANTI: "right anti",
    }

    def _pa_join_eligible(self) -> bool:
        # residual filters and NOT-IN null semantics keep the shared
        # vectorized probe; EXISTENCE has no Acero equivalent
        return (self.join_filter is None and not self.null_aware_anti
                and self.join_type in self._PA_JOIN_TYPES)

    def _join_key_table(self, plan_schema: Schema, rb_or_tbl, keys,
                        prefix: str):
        """Rename columns positionally ({prefix}{i}) and append computed
        join-key columns (__k{i}) so arbitrary key exprs and duplicate
        names across sides both work.  Float keys normalize -0.0 -> 0.0
        and NaN -> one canonical pattern (Acero hashes raw bits; Spark's
        NormalizeFloatingNumbers runs upstream of the join)."""
        from blaze_tpu.exprs.base import BoundReference
        tbl = (pa.Table.from_batches([rb_or_tbl])
               if isinstance(rb_or_tbl, pa.RecordBatch) else rb_or_tbl)
        n = tbl.num_rows
        cb = None
        key_cols = []
        for e in keys:
            if isinstance(e, BoundReference):
                arr = tbl.column(e.index)  # zero-copy; no batch rebuild
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.combine_chunks()
            else:
                if cb is None:
                    cb = ColumnBatch.from_arrow(tbl.combine_chunks())
                arr = e.evaluate(cb).to_host(n)
            if pa.types.is_floating(arr.type):
                arr = pc.add(arr, 0.0)  # -0.0 + 0.0 == +0.0
                nan = pa.scalar(float("nan"), type=arr.type)
                arr = pc.if_else(pc.is_nan(arr), nan, arr)
            key_cols.append(arr)
        arrays = list(tbl.columns) + key_cols
        names = [f"{prefix}{i}" for i in range(tbl.num_columns)] + \
            [f"__{prefix}k{i}" for i in range(len(keys))]
        return pa.table(arrays, names=names)

    def _pa_join(self, jmap: JoinMap, partition: int, probe, probe_keys,
                 probe_is_left: bool) -> Iterator[ColumnBatch]:
        """One Acero join over the collected probe side.  If the probe
        exceeds the collect budget, switch to the streaming JoinMap probe
        instead of re-running Acero per chunk — Acero rebuilds its
        build-side hash table on every Table.join call, while JoinMap
        hashes the build side exactly once."""
        limit = config.FUSED_HOST_COLLECT_ROWS.get()
        build_is_left = not probe_is_left
        build_keys = self.left_keys if build_is_left else self.right_keys
        build_tbl = self._join_key_table(
            jmap.schema, jmap.table, build_keys,
            "l" if build_is_left else "r")
        # the build side is materialized BEFORE probe collection, so the
        # join-key runtime filter applies DURING collection: probe rows
        # outside the build key range never occupy collect memory (and a
        # selective filter keeps large probes under the collect limit
        # instead of tipping them onto the streaming path)
        prefilter, covered, conjuncts = self._collect_prefilter(
            build_tbl, probe.schema, probe_keys, probe_is_left)
        chunks: List[pa.RecordBatch] = []
        rows = 0
        # Arrow-resident collection: sources that hold Arrow data (scans)
        # stream it straight through without a ColumnBatch round trip;
        # parquet probes additionally row-group-prune by the runtime
        # filter for THIS read only
        stream = probe.execute_pruned(partition, conjuncts, arrow=True)
        overflowed = False
        for rb in stream:
            if prefilter is not None and rb.num_rows:
                rb = prefilter(rb)
            if rb.num_rows == 0:
                continue
            chunks.append(rb)
            rows += rb.num_rows
            if rows >= limit:
                overflowed = True
                break
        if overflowed:
            yield from self._stream_probe(
                jmap,
                (ColumnBatch.from_arrow(b) for b in
                 itertools.chain(chunks, stream)),
                probe_keys, probe_is_left)
            return
        yield from self._pa_join_once(build_tbl, chunks, probe_keys,
                                      probe_is_left, skip_filter_keys=covered)

    @staticmethod
    def _key_range_conjuncts(ranges, schema: Schema, probe_keys) -> list:
        """What the build side's `key_ranges` say of every probe row that
        can match, as conditions over the probe's `schema` for
        `execute_pruned` to hand to the probe's parquet scan - with
        date-clustered fact tables whole row groups outside the build key
        range are never decoded (the reference pushes its bloom runtime
        filters into the probe scan the same way:
        bloom_filter_might_contain.rs + parquet page filtering): a key
        that is a plain column of the probe lies inside the build side's
        [min, max] of it, and nothing matches a build side without a
        key.  Row-exact filtering stays with the join."""
        from blaze_tpu.exprs.base import BoundReference, Literal
        from blaze_tpu.exprs.binary import BinaryExpr
        from blaze_tpu.exprs.conditional import InList
        cols = [(i, BoundReference(e.index, schema[e.index].name),
                 schema[e.index].data_type)
                for i, e in enumerate(probe_keys)
                if isinstance(e, BoundReference) and e.index < len(schema)]
        if ranges is None:
            # IN (): statistics of any kind prove a row group empty
            return [InList(col, ()) for _i, col, _t in cols[:1]]
        out = []
        for i, col, dtype in cols:
            if ranges[i] is not None:
                mn, mx = ranges[i]
                out += [BinaryExpr(">=", col, Literal(mn.as_py(), dtype)),
                        BinaryExpr("<=", col, Literal(mx.as_py(), dtype))]
        return out

    def _runtime_filter_drop_ok(self, probe_is_left: bool) -> bool:
        """Whether dropping never-matching probe rows is semantics-
        preserving: inner joins and probe-side semi joins only."""
        jt = self.join_type
        return (jt == JoinType.INNER or
                (jt == JoinType.LEFT_SEMI and probe_is_left) or
                (jt == JoinType.RIGHT_SEMI and not probe_is_left))

    def _runtime_filter_on(self, probe_is_left: bool) -> bool:
        return (self._runtime_filter_drop_ok(probe_is_left)
                and config.JOIN_RUNTIME_FILTER_ENABLE.get())

    @staticmethod
    def _range_mask(col, mn, mx):
        return pc.and_(pc.greater_equal(col, mn), pc.less_equal(col, mx))

    def _collect_prefilter(self, build_tbl, probe_schema: Schema,
                           probe_keys, probe_is_left: bool):
        """(closure, covered, conjuncts): the closure drops probe rows
        outside the build side's join-key [min, max] ranges (`key_ranges`:
        integer and date keys that are plain probe columns), applied
        batch-by-batch while the probe is being collected; `covered`
        lists the key positions it handled so the join-time filter skips
        them; `conjuncts` say the same of the probe's rows for its scan
        to prune row groups by (`_key_range_conjuncts`).
        (None, frozenset(), []) when inapplicable (non-droppable join
        type, computed or other keys)."""
        none = (None, frozenset(), [])
        if not self._runtime_filter_on(probe_is_left):
            return none
        from blaze_tpu.exprs.base import BoundReference
        bprefix = "l" if not probe_is_left else "r"
        ranges = key_ranges(
            build_tbl.num_rows,
            [build_tbl.column(f"__{bprefix}k{i}")
             for i in range(len(probe_keys))])
        conjuncts = self._key_range_conjuncts(ranges, probe_schema,
                                              probe_keys)
        metrics = self.metrics
        if ranges is None:
            def drop_all(rb):
                metrics.add("runtime_filter_pruned", rb.num_rows)
                return rb.slice(0, 0)
            return drop_all, frozenset(range(len(probe_keys))), conjuncts
        ranges = [(i, e.index) + ranges[i]
                  for i, e in enumerate(probe_keys)
                  if isinstance(e, BoundReference) and ranges[i] is not None]
        if not ranges:
            return none

        def apply(rb):
            mask = None
            for _k, idx, mn, mx in ranges:
                m = self._range_mask(rb.column(idx), mn, mx)
                mask = m if mask is None else pc.and_kleene(mask, m)
            out = rb.filter(mask)
            metrics.add("runtime_filter_pruned",
                        rb.num_rows - out.num_rows)
            return out
        return apply, frozenset(k for k, *_r in ranges), conjuncts

    def _runtime_filter_probe(self, build_tbl, probe_tbl, pprefix: str,
                              probe_is_left: bool,
                              skip_keys: frozenset = frozenset()):
        """Join-key runtime filter: before probing, drop probe rows whose
        integer key falls outside the build side's [min, max] — the
        engine-side analog of the reference's runtime-filter joins
        (bloom_filter agg + bloom_filter_might_contain.rs pushed into the
        probe scan).  One vectorized comparison pass over the probe
        replaces hash-probing every row that cannot possibly match.

        Only join types where a non-matching probe row produces no output
        may drop rows (inner, probe-side semi); null keys never match an
        equi-join, so the null-dropping comparison semantics are exact."""
        if (not self._runtime_filter_drop_ok(probe_is_left)
                or not config.JOIN_RUNTIME_FILTER_ENABLE.get()
                or probe_tbl.num_rows == 0):
            return probe_tbl
        if build_tbl.num_rows == 0:
            return probe_tbl.slice(0, 0)  # inner/semi vs empty build
        bprefix = "r" if pprefix == "l" else "l"
        for i in range(len(self.left_keys)):
            if i in skip_keys:  # already pruned during probe collection
                continue
            bcol = build_tbl.column(f"__{bprefix}k{i}")
            if not pa.types.is_integer(bcol.type):
                continue
            mm = pc.min_max(bcol)
            if not mm["min"].is_valid:
                probe_tbl = probe_tbl.slice(0, 0)  # all-null build keys
                break
            before = probe_tbl.num_rows
            probe_tbl = probe_tbl.filter(self._range_mask(
                probe_tbl.column(f"__{pprefix}k{i}"),
                mm["min"], mm["max"]))
            self.metrics.add("runtime_filter_pruned",
                             before - probe_tbl.num_rows)
            if probe_tbl.num_rows == 0:
                break
        return probe_tbl

    # span cap for the direct-address build table (slots are int64:
    # 32 MB at the cap) and a density floor so sparse key sets still
    # take the hash join
    _DIRECT_SPAN_MAX = 1 << 22
    _DIRECT_BUILD_MAX = 1 << 20

    def _direct_join_once(self, build_tbl, probe_tbl, probe_is_left):
        """Single-integer-key join via a DIRECT-ADDRESS table.

        Dimension keys in star schemas (date_sk, item_sk, store_sk...)
        are dense contiguous ranges; Acero re-hashes the build side on
        every Table.join call, while a slot array indexed by `key - min`
        resolves each probe row with one subtract + one gather — the
        same dense-key strategy the fused aggregation uses
        (plan/fused.py dense group ids).  Applies to probe-driven join
        types with a UNIQUE build key (each probe row matches at most
        one build row, so output needs no pair expansion).  Returns a
        joined table shaped exactly like the Acero result (l{i}/r{i}
        columns), or None -> Acero fallback.
        """
        jt = self.join_type
        eligible = {JoinType.INNER}
        if probe_is_left:
            eligible |= {JoinType.LEFT, JoinType.LEFT_SEMI,
                         JoinType.LEFT_ANTI}
        else:
            eligible |= {JoinType.RIGHT, JoinType.RIGHT_SEMI,
                         JoinType.RIGHT_ANTI}
        if jt not in eligible or len(self.left_keys) != 1:
            return None
        pprefix = "l" if probe_is_left else "r"
        bprefix = "r" if probe_is_left else "l"
        bk = build_tbl.column(f"__{bprefix}k0")
        pk = probe_tbl.column(f"__{pprefix}k0")
        if not (pa.types.is_integer(bk.type) and
                pa.types.is_integer(pk.type)):
            return None
        if any(pa.types.is_unsigned_integer(t) and t.bit_width == 64
               for t in (bk.type, pk.type)):
            # uint64 beyond int64 range wraps in the astype(int64)
            # below; a wrapped PROBE value could silently false-match
            # an in-range build key, so both sides are rejected
            return None
        if build_tbl.num_rows > self._DIRECT_BUILD_MAX:
            return None
        bk = bk.combine_chunks() if isinstance(bk, pa.ChunkedArray) else bk
        pk = pk.combine_chunks() if isinstance(pk, pa.ChunkedArray) else pk
        bnp = bk.drop_null().to_numpy(zero_copy_only=False).astype(
            np.int64, copy=False)
        b_rows = (np.flatnonzero(bk.is_valid().to_numpy(
            zero_copy_only=False)) if bk.null_count
            else np.arange(len(bnp)))
        n_probe_cols = probe_tbl.num_columns - 1
        n_build_cols = build_tbl.num_columns - 1
        probe_cols = probe_tbl.columns[:n_probe_cols]
        probe_names = probe_tbl.column_names[:n_probe_cols]
        build_cols = build_tbl.columns[:n_build_cols]
        build_names = build_tbl.column_names[:n_build_cols]
        semi_anti = jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                           JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI)
        anti = jt in (JoinType.LEFT_ANTI, JoinType.RIGHT_ANTI)
        outer = jt in (JoinType.LEFT, JoinType.RIGHT)
        if bnp.size == 0:
            if anti or outer:
                b = np.full(probe_tbl.num_rows, -1, np.int64)
                match = np.zeros(probe_tbl.num_rows, bool)
            else:
                # fast-path engagement stays observable on this branch
                self.metrics.add("direct_join_rows", 0)
                return pa.table(
                    [c.slice(0, 0) for c in probe_cols] +
                    ([] if semi_anti else
                     [c.slice(0, 0) for c in build_cols]),
                    names=probe_names +
                    ([] if semi_anti else build_names))
        else:
            mn = int(bnp.min())
            mx = int(bnp.max())
            span = mx - mn + 1
            if span > self._DIRECT_SPAN_MAX or (
                    span > 65536 and span > 64 * bnp.size):
                # span cap + density floor: a sparse key set would pay
                # an O(span) slot array to serve few build rows
                return None
            slot = np.full(span, -1, np.int64)
            slot[bnp - mn] = b_rows
            # uniqueness: a duplicate key overwrites its first slot, so
            # the number of occupied slots betrays duplicates in O(span)
            if int((slot >= 0).sum()) != bnp.size:
                return None
            if pk.null_count:
                pvalid = pk.is_valid().to_numpy(zero_copy_only=False)
                pnp = pk.fill_null(0).to_numpy(
                    zero_copy_only=False).astype(np.int64, copy=False)
            else:
                pvalid = None
                pnp = pk.to_numpy(zero_copy_only=False).astype(
                    np.int64, copy=False)
            # range-test BEFORE subtracting: comparisons are exact while
            # pnp - mn can wrap int64 for extreme key ranges (a wrapped
            # index landing in [0, span) would be a silent false match);
            # clipping first keeps the subtraction in-bounds, and filled
            # nulls (0) are masked by pvalid regardless of range
            inr = (pnp >= mn) & (pnp <= mx)
            if pvalid is not None:
                inr &= pvalid
            idx = np.clip(pnp, mn, mx) - mn
            b = np.where(inr, slot[idx], np.int64(-1))
            match = b >= 0
        if semi_anti:
            sel = np.flatnonzero(~match if anti else match)
            tbl = pa.table(probe_cols, names=probe_names)
            self.metrics.add("direct_join_rows", len(sel))
            return tbl.take(pa.array(sel))
        if outer:
            p_sel = None  # every probe row survives
            b_idx = pa.array(b, mask=~match)
        else:  # inner
            p_sel = np.flatnonzero(match)
            b_idx = pa.array(b[match])
        ptbl = pa.table(probe_cols, names=probe_names)
        if p_sel is not None:
            ptbl = ptbl.take(pa.array(p_sel))
        taken = [pc.take(c, b_idx) for c in build_cols]
        arrays = list(ptbl.columns) + taken
        names = list(probe_names) + list(build_names)
        if not probe_is_left:
            arrays = taken + list(ptbl.columns)
            names = list(build_names) + list(probe_names)
        self.metrics.add("direct_join_rows", len(b_idx))
        return pa.table(arrays, names=names)

    def _pa_join_once(self, build_tbl, probe_chunks, probe_keys,
                      probe_is_left: bool,
                      skip_filter_keys: frozenset = frozenset()
                      ) -> Iterator[ColumnBatch]:
        rows = sum(c.num_rows for c in probe_chunks)
        xla_stats.note_join_probe(current_task().device_id, False, rows)
        with tracing.span("join_probe", lane="arrow", rows=rows):
            rb = self._pa_join_table(build_tbl, probe_chunks, probe_keys,
                                     probe_is_left, skip_filter_keys)
        bs = config.BATCH_SIZE.get()
        for off in range(0, rb.num_rows, bs):
            yield ColumnBatch.from_arrow(
                rb.slice(off, min(bs, rb.num_rows - off)))

    def _pa_join_table(self, build_tbl, probe_chunks, probe_keys,
                       probe_is_left: bool, skip_filter_keys: frozenset
                       ) -> pa.RecordBatch:
        probe_schema = self.children[0 if probe_is_left else 1].schema
        pprefix = "l" if probe_is_left else "r"
        if probe_chunks:
            probe_pa = pa.Table.from_batches(probe_chunks)
        else:
            probe_pa = pa.Table.from_batches(
                [], schema=probe_schema.to_arrow())
        probe_tbl = self._join_key_table(probe_schema, probe_pa,
                                         probe_keys, pprefix)
        probe_tbl = self._runtime_filter_probe(build_tbl, probe_tbl,
                                               pprefix, probe_is_left,
                                               skip_keys=skip_filter_keys)
        joined = self._direct_join_once(build_tbl, probe_tbl,
                                        probe_is_left)
        if joined is None:
            left_tbl = probe_tbl if probe_is_left else build_tbl
            right_tbl = build_tbl if probe_is_left else probe_tbl
            lk = [f"__lk{i}" for i in range(len(self.left_keys))]
            rk = [f"__rk{i}" for i in range(len(self.right_keys))]
            joined = left_tbl.join(
                right_tbl, keys=lk, right_keys=rk,
                join_type=self._PA_JOIN_TYPES[self.join_type],
                use_threads=True)
        out_arrow = self.schema.to_arrow()
        jt = self.join_type
        if jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            names = [f"l{i}"
                     for i in range(len(self.children[0].schema))]
        elif jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            names = [f"r{i}"
                     for i in range(len(self.children[1].schema))]
        else:
            names = [f"l{i}"
                     for i in range(len(self.children[0].schema))] + \
                    [f"r{i}" for i in range(len(self.children[1].schema))]
        arrays = []
        for name, f in zip(names, out_arrow):
            col = joined.column(name)
            if isinstance(col, pa.ChunkedArray):
                col = col.combine_chunks()
            if not col.type.equals(f.type):
                col = col.cast(f.type, safe=False)
            arrays.append(col)
        return pa.RecordBatch.from_arrays(arrays, schema=out_arrow)

    # -- probe one batch ----------------------------------------------------
    def _probe_batch(self, jmap: JoinMap, batch: ColumnBatch,
                     probe_keys: Sequence[PhysicalExpr], probe_is_left: bool
                     ) -> List[ColumnBatch]:
        """One probe batch from hashed keys to joined batch (0 or 1
        output batches)."""
        with tracing.span("join_probe", rows=batch.num_rows, lane="host"):
            return list(self._probe_batch_rows(jmap, batch, probe_keys,
                                               probe_is_left))

    def _probe_batch_rows(self, jmap: JoinMap, batch: ColumnBatch,
                          probe_keys: Sequence[PhysicalExpr],
                          probe_is_left: bool) -> Iterator[ColumnBatch]:
        n = batch.num_rows
        hashes, any_null, key_arrays = _device_hash_keys(batch, probe_keys)
        p_idx, b_idx = jmap.lookup(hashes, any_null, key_arrays)
        probe_rb = batch.to_arrow()

        if self.join_filter is not None and len(p_idx):
            mask = self._apply_filter(probe_rb, jmap, p_idx, b_idx,
                                      probe_is_left)
            p_idx, b_idx = p_idx[mask], b_idx[mask]

        jt = self.join_type
        jmap.matched[b_idx] = True
        match_count = np.bincount(p_idx, minlength=n)

        probe_semi = ((jt == JoinType.LEFT_SEMI and probe_is_left) or
                      (jt == JoinType.RIGHT_SEMI and not probe_is_left))
        probe_anti = ((jt == JoinType.LEFT_ANTI and probe_is_left) or
                      (jt == JoinType.RIGHT_ANTI and not probe_is_left))
        if probe_anti and self.null_aware_anti and jmap.num_rows:
            if jmap.has_null_keys:
                return  # NULL in the IN-list: nothing ever qualifies
            # NOT IN over a non-empty list: a NULL probe key is UNKNOWN.
            # (empty build side falls through: x NOT IN () is TRUE even
            # for NULL x, so the plain anti path below keeps every row)
            keep = np.nonzero((match_count == 0) & ~any_null)[0]
            if len(keep):
                yield ColumnBatch.from_arrow(
                    probe_rb.take(pa.array(keep, type=pa.int64())))
            return
        if probe_semi or probe_anti:
            keep = np.nonzero(match_count > 0 if probe_semi
                              else match_count == 0)[0]
            if len(keep):
                yield ColumnBatch.from_arrow(
                    probe_rb.take(pa.array(keep, type=pa.int64())))
            return
        if jt in (JoinType.LEFT_SEMI, JoinType.RIGHT_SEMI,
                  JoinType.LEFT_ANTI, JoinType.RIGHT_ANTI):
            # semi/anti of the BUILD side: probe only records matches;
            # emission happens in _emit_unmatched_build
            return
        if jt == JoinType.EXISTENCE:
            arrays = list(probe_rb.columns) + \
                [pa.array(match_count > 0, type=pa.bool_())]
            yield ColumnBatch.from_arrow(pa.RecordBatch.from_arrays(
                arrays, schema=self.schema.to_arrow()))
            return

        # inner/outer: matched pairs
        outer_probe = (jt == JoinType.FULL or
                       (jt == JoinType.LEFT and probe_is_left) or
                       (jt == JoinType.RIGHT and not probe_is_left))
        if outer_probe:
            un = np.nonzero(match_count == 0)[0]
            if len(un):
                p_idx = np.concatenate([p_idx, un])
                b_idx = np.concatenate([b_idx,
                                        np.full(len(un), -1, dtype=np.int64)])
        if not len(p_idx):
            return
        yield self._materialize(probe_rb, jmap, p_idx, b_idx, probe_is_left)

    def _apply_filter(self, probe_rb, jmap: JoinMap, p_idx, b_idx,
                      probe_is_left) -> np.ndarray:
        joined = self._joined_batch(probe_rb, jmap, p_idx, b_idx,
                                    probe_is_left, allow_missing=False)
        v = self.join_filter.evaluate(joined)
        return asnp(v.as_mask(joined))[:joined.num_rows]

    def _joined_batch(self, probe_rb, jmap, p_idx, b_idx, probe_is_left,
                      allow_missing=True) -> ColumnBatch:
        pt = probe_rb.take(pa.array(p_idx, type=pa.int64()))
        bi = pa.array(b_idx, type=pa.int64())
        if jmap.num_rows == 0:
            bt_cols = [pa.nulls(len(b_idx), f.data_type.to_arrow())
                       for f in jmap.schema]
        elif allow_missing and (b_idx < 0).any():
            bi = pa.array(np.where(b_idx < 0, 0, b_idx), type=pa.int64())
            bt = jmap.table.take(bi)
            null_mask = b_idx < 0
            bt_cols = [_null_out(c, null_mask) for c in bt.columns]
        else:
            bt = jmap.table.take(bi)
            bt_cols = [c.combine_chunks() if isinstance(c, pa.ChunkedArray)
                       else c for c in bt.columns]
        left_cols = (list(pt.columns) if probe_is_left else bt_cols)
        right_cols = (bt_cols if probe_is_left else list(pt.columns))
        arrays = left_cols + right_cols
        out_schema = self.schema if self.join_type in (
            JoinType.INNER, JoinType.LEFT, JoinType.RIGHT, JoinType.FULL) \
            else Schema(list(self.children[0].schema) +
                        list(self.children[1].schema))
        arrays = [a.combine_chunks() if isinstance(a, pa.ChunkedArray) else a
                  for a in arrays]
        rb = pa.RecordBatch.from_arrays(
            [a.cast(f.data_type.to_arrow(), safe=False)
             if not a.type.equals(f.data_type.to_arrow()) else a
             for a, f in zip(arrays, out_schema)],
            schema=out_schema.to_arrow())
        return ColumnBatch.from_arrow(rb)

    def _materialize(self, probe_rb, jmap, p_idx, b_idx, probe_is_left
                     ) -> ColumnBatch:
        return self._joined_batch(probe_rb, jmap, p_idx, b_idx, probe_is_left)

    def _emit_unmatched_build(self, jmap: JoinMap, probe_is_left: bool
                              ) -> Iterator[ColumnBatch]:
        jt = self.join_type
        build_outer = (jt == JoinType.FULL or
                       (jt == JoinType.RIGHT and probe_is_left) or
                       (jt == JoinType.LEFT and not probe_is_left))
        build_semi = ((jt == JoinType.RIGHT_SEMI and probe_is_left) or
                      (jt == JoinType.LEFT_SEMI and not probe_is_left))
        build_anti = ((jt == JoinType.RIGHT_ANTI and probe_is_left) or
                      (jt == JoinType.LEFT_ANTI and not probe_is_left))
        if build_semi or build_anti:
            want = jmap.matched if build_semi else ~jmap.matched
            idx = np.nonzero(want)[0]
            if len(idx):
                rb = jmap.table.take(pa.array(idx, type=pa.int64())) \
                    .combine_chunks()
                yield ColumnBatch.from_arrow(rb.to_batches()[0])
            return
        if not build_outer or jmap.num_rows == 0:
            return
        idx = np.nonzero(~jmap.matched)[0]
        if not len(idx):
            return
        bt = jmap.table.take(pa.array(idx, type=pa.int64()))
        probe_schema = self.children[0 if probe_is_left else 1].schema
        null_probe = [pa.nulls(len(idx), f.data_type.to_arrow())
                      for f in probe_schema]
        bt_cols = [c.combine_chunks() if isinstance(c, pa.ChunkedArray) else c
                   for c in bt.columns]
        arrays = (null_probe + bt_cols) if probe_is_left else \
            (bt_cols + null_probe)
        rb = pa.RecordBatch.from_arrays(arrays, schema=self.schema.to_arrow())
        yield ColumnBatch.from_arrow(rb)


def _null_out(col, null_mask: np.ndarray) -> pa.Array:
    col = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    return pc.if_else(pa.array(~null_mask), col,
                      pa.nulls(len(col), col.type))


class SortMergeJoinExec(BaseJoinExec):
    """Streaming merge join (ref sort_merge_join_exec.rs:397 +
    joins/smj/*, joins/stream_cursor.rs).

    Children are consumed key-sorted (ascending, nulls first).  A child
    that is already a SortExec on the join keys streams straight through
    (the converter contract — childOrderingRequiredTag — guarantees sorts
    in translated plans); otherwise a spillable SortExec is inserted, so
    hand-built plans stay correct and the sort inherits the external-sort
    memory discipline."""

    def _sorted_child(self, side: int) -> ExecutionPlan:
        from blaze_tpu.ops.sort import SortExec
        child = self.children[side]
        keys = self.left_keys if side == 0 else self.right_keys
        if isinstance(child, SortExec):
            specs = child._specs
            if len(specs) >= len(keys) and all(
                    s[0].cache_key() == k.cache_key() and not s[1] and s[2]
                    for s, k in zip(specs, keys)):
                return child
        return SortExec(child, [(k, False, True) for k in keys])

    def _acero_eligible(self) -> bool:
        """Arrow's hash join takes the join type, and the keys are plain
        columns (EXISTENCE is already outside _PA_JOIN_TYPES)."""
        from blaze_tpu.exprs.base import BoundReference
        return self._pa_join_eligible() and all(
            isinstance(k, BoundReference)
            for k in self.left_keys + self.right_keys)

    def _acero_join(self, left: List[pa.RecordBatch],
                    right: List[pa.RecordBatch]) -> Iterator[ColumnBatch]:
        """Both collected sides through Arrow's C++ hash join, the OUTPUT
        re-sorted by the join keys (ascending, nulls first) to preserve
        SMJ's output-ordering contract for downstream consumers.  A
        run-cursor merge over N one-row key runs is O(N) Python; this
        replaces it with two vectorized passes (the q97 distinct-pair FULL
        OUTER was 200x slower streaming)."""
        build_tbl = self._join_key_table(
            self.children[1].schema,
            pa.Table.from_batches(
                right, schema=self.children[1].schema.to_arrow()),
            self.right_keys, "r")
        out = list(self._pa_join_once(build_tbl, left, self.left_keys, True))
        if not out:
            return
        tbl = pa.Table.from_batches([cb.compact().to_arrow() for cb in out])
        order = self._smj_output_order(tbl)
        if order is not None:
            tbl = tbl.take(order)
        bs = config.BATCH_SIZE.get()
        for off in range(0, tbl.num_rows, bs):
            yield ColumnBatch.from_arrow(
                tbl.slice(off, min(bs, tbl.num_rows - off)).combine_chunks())

    def _smj_output_order(self, tbl):
        """Sort indices restoring key order (nulls first).  Key columns
        live at the BoundReference positions of whichever side(s) the
        output carries; FULL/RIGHT joins coalesce left/right keys (the
        unmatched side's key is null)."""
        jt = self.join_type
        nl = len(self.children[0].schema)
        if jt in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            keys = [tbl.column(k.index) for k in self.right_keys]
        elif jt in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
                    JoinType.INNER, JoinType.LEFT):
            keys = [tbl.column(k.index) for k in self.left_keys]
        elif jt in (JoinType.RIGHT, JoinType.FULL):
            keys = [pc.coalesce(tbl.column(lk.index),
                                tbl.column(nl + rk.index))
                    for lk, rk in zip(self.left_keys, self.right_keys)]
        else:
            return None
        kt = pa.table(keys, names=[f"k{i}" for i in range(len(keys))])
        return pc.sort_indices(
            kt, sort_keys=[(f"k{i}", "ascending")
                           for i in range(len(keys))],
            null_placement="at_start")

    def execute(self, partition: int) -> BatchIterator:
        from blaze_tpu.bridge.placement import host_resident
        if host_resident():
            return self._merge_host(partition)
        return iter(CoalesceStream(self._merge_device(partition),
                                   metrics=self.metrics))

    def _merge_host(self, partition: int) -> Iterator[ColumnBatch]:
        """Host placement: both sorted sides within the collect budget
        (`auron.tpu.fused.hostCollectRows`, per side) join through Arrow
        (`_acero_join`).  A side over it (the spillable sorts exist
        precisely for that), computed keys, or a join type Arrow lacks go
        through the run cursor, resumed from what was collected."""
        streams = [_arrow_stream(self._sorted_child(i).execute(partition))
                   for i in (0, 1)]
        held: List[List[pa.RecordBatch]] = [[], []]
        limit = config.FUSED_HOST_COLLECT_ROWS.get()

        def sides_fit() -> bool:
            for side, stream in zip(held, streams):
                rows = 0
                for rb in stream:
                    side.append(rb)
                    rows += rb.num_rows
                    if rows > limit:
                        return False
            return True

        if self._acero_eligible() and sides_fit():
            # output_rows is counted inside _pa_join_once already
            yield from self._acero_join(*held)
            return
        yield from CoalesceStream(
            self._merge_streaming(*(itertools.chain(side, stream)
                                    for side, stream in zip(held, streams))),
            metrics=self.metrics)

    def _merge_device(self, partition: int) -> Iterator[ColumnBatch]:
        """The partition through ops/joins/merge.py: both sorted sides
        collected on the device, their bytes and the pairs' declared to the
        memory manager (`merge.Hold`), joined by device programs.  A
        partition the manager sheds, and a join shape `merge.declines`
        names, go through the run cursor instead, resumed from what was
        collected (both children are sorted)."""
        from blaze_tpu.memory import MemManager
        from blaze_tpu.ops.joins import merge
        streams = [self._sorted_child(i).execute(partition) for i in (0, 1)]
        held: List[List[ColumnBatch]] = [[], []]
        hold = merge.Hold(self.metrics)
        hold.set_spillable(MemManager.get())

        def sides_fit() -> bool:
            for side, stream in zip(held, streams):
                for b in stream:
                    b = b.compact()
                    if b.num_rows:
                        side.append(b)
                        if not hold.reserve(b.nbytes_device()):
                            return False
            return True

        try:
            if merge.declines(self) is None and sides_fit():
                try:
                    out = merge.join(self, held[0], held[1], hold)
                except merge.Over:
                    pass  # the pairs were denied their bytes
                else:
                    if out is not None:
                        yield out
                    return
            self.metrics.add("smj_streamed", 1)
            # down a tier before anything else is held: the run cursor
            # reads Arrow
            rest = [itertools.chain([b.to_arrow() for b in side],
                                    _arrow_stream(stream))
                    for side, stream in zip(held, streams)]
            del held[:]
            hold.update_mem_used(0)
            yield from self._merge_streaming(*rest)
        finally:
            hold.unregister()

    def _merge_streaming(self, l_stream, r_stream) -> Iterator[ColumnBatch]:
        """The run-cursor merge over two key-sorted Arrow streams."""
        from blaze_tpu.ops.joins.smj import MergeJoiner, _RunCursor
        joiner = MergeJoiner(self.children[0].schema,
                             self.children[1].schema, self.schema,
                             self.join_type, self.join_filter,
                             self._existence_col)
        lcur = _RunCursor(l_stream, self.left_keys,
                          self.children[0].schema)
        rcur = _RunCursor(r_stream, self.right_keys,
                          self.children[1].schema)
        for rb in joiner.join(lcur, rcur):
            yield ColumnBatch.from_arrow(rb)


def _arrow_stream(batches) -> Iterator[pa.RecordBatch]:
    for b in batches:
        rb = b.compact().to_arrow()
        if rb.num_rows:
            yield rb


class ShuffledHashJoinExec(BaseJoinExec):
    """SHJ parity node: build side = one shuffled partition.  When
    `auron.smjfallback.enable` is set and the build side exceeds the
    rows/bytes thresholds while materializing, the partition re-executes
    as a streaming sort-merge join (ref smjfallback confs,
    SparkAuronConfiguration.java:231-250)."""

    def execute(self, partition: int) -> BatchIterator:
        if not config.SMJ_FALLBACK_ENABLE.get():
            yield from super().execute(partition)
            return
        build = 1 if self.build_side == "right" else 0
        child = self.children[build]
        row_cap = config.SMJ_FALLBACK_ROWS_THRESHOLD.get()
        mem_cap = config.SMJ_FALLBACK_MEM_THRESHOLD.get()
        batches: List[pa.RecordBatch] = []
        rows = nbytes = 0
        overflowed = False
        for b in child.execute(partition):
            rb = b.compact().to_arrow()
            if rb.num_rows == 0:
                continue
            batches.append(rb)
            rows += rb.num_rows
            nbytes += rb.nbytes
            if rows > row_cap or nbytes > mem_cap:
                overflowed = True
                break
        if overflowed:
            # abandon the hash build; re-run this partition as SMJ
            self.metrics.add("smj_fallback", 1)
            del batches
            smj = SortMergeJoinExec(
                self.children[0], self.children[1], self.left_keys,
                self.right_keys, self.join_type,
                build_side=self.build_side, join_filter=self.join_filter,
                existence_col=self._existence_col,
                null_aware_anti=self.null_aware_anti)
            smj.metrics = self.metrics
            yield from smj.execute(partition)
            return
        keys = self.right_keys if build == 1 else self.left_keys
        jmap = build_join_map(iter(batches), child.schema, keys)
        yield from self._probe_with_map(jmap, partition)


class BroadcastJoinExec(BaseJoinExec):
    """BHJ: build side materialized once per broadcast and cached in the
    resource map (ref broadcast_join_exec.rs:695 cached_build_hash_map)."""

    def __init__(self, *args, broadcast_id: Optional[str] = None, **kw):
        super().__init__(*args, **kw)
        # default ids must be process-unique FOREVER, not id(self):
        # CPython reuses freed addresses, and a recycled id would serve a
        # stale build map out of the long-lived resource-map cache
        self._broadcast_id = broadcast_id or f"bhj-{next(_local_bid)}"

    def _get_join_map(self, partition: int) -> JoinMap:
        build = 1 if self.build_side == "right" else 0
        child = self.children[build]

        def factory():
            keys = self.right_keys if build == 1 else self.left_keys
            batches = []
            with tracing.span("join_build", partition=partition,
                              step="collect"):
                for p in range(child.num_partitions):
                    batches.extend(b.compact().to_arrow(keep_dict=True)
                                   for b in child.execute(p))
                return build_join_map(iter(batches), child.schema, keys)
        # the cache key folds the build-side output schema: plan rewrites
        # (column pruning) may narrow the build columns per consumer, and
        # two plans sharing one broadcast_id must not serve each other
        # positionally-different build tables
        sig = ",".join(f.name for f in child.schema)
        return get_or_create(
            f"join_map://{self._broadcast_id}/{hash(sig) & 0xffffffff:x}",
            factory)


class BuildHashMapExec(ExecutionPlan):
    """Broadcast build-map stage (ref broadcast_join_build_hash_map_exec.rs):
    materializes the build side once per broadcast so downstream
    BroadcastJoinExec tasks can share it through the resource-map cache.
    Batches stream through unchanged; the map is built as a side effect the
    first time any consumer pulls the stage."""

    def __init__(self, child: ExecutionPlan, keys: Sequence[PhysicalExpr],
                 cache_id: Optional[str] = None):
        super().__init__([child])
        self.keys = list(keys)
        self.cache_id = cache_id

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int) -> BatchIterator:
        child = self.children[0]
        if not self.cache_id:  # no consumer to share with: stream through
            yield from child.execute(partition)
            return
        batches = [b.compact() for b in child.execute(partition)]
        arrow = [b.to_arrow() for b in batches]
        get_or_create(
            f"join_map://{self.cache_id}",
            lambda: build_join_map(iter(arrow), child.schema, self.keys))
        yield from iter(batches)
