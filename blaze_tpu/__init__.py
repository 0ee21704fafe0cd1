"""blaze_tpu — a TPU-native query-execution engine with the capabilities of
Apache Auron (formerly Blaze).

Auron intercepts optimized Spark/Flink physical plans, ships them as protobuf
into a native engine, and executes them with vectorized columnar kernels
(reference: /root/reference/README.md:30-46).  blaze_tpu provides the same
capability re-designed TPU-first: plans decode into a DAG of operators whose
hot paths are `jax.jit`-compiled XLA/Pallas programs over statically-shaped
columnar batches, scaled across chips with `jax.sharding` meshes and XLA
collectives instead of shuffle-file RPC where possible.

Layer map (mirrors SURVEY.md §1):
  - plan/     : plan IR + serde + planner   (ref: native-engine/auron-planner)
  - ops/      : execution operators         (ref: datafusion-ext-plans)
  - exprs/    : expression evaluation       (ref: datafusion-ext-exprs)
  - funcs/    : spark-semantics functions   (ref: datafusion-ext-functions)
  - kernels/  : shared kernels              (ref: datafusion-ext-commons)
  - shuffle/  : repartitioners + IPC files  (ref: datafusion-ext-plans/src/shuffle)
  - memory/   : memory budget + spill       (ref: auron-memmgr)
  - parallel/ : mesh / collective exchange  (TPU-native: ICI all-to-all, psum)
  - bridge/   : host runtime + resource map (ref: auron/ + auron-jni-bridge)
"""

import os

import jax

# 64-bit dtypes are load-bearing for this domain: Arrow int64 keys, Spark
# xxhash64, decimal128 unscaled values.  The TPU backend supports
# i64/u64/f64 (emulated where needed), so enable globally before any tracing.
jax.config.update("jax_enable_x64", True)

# Persistent XLA compile cache.  Set up HERE, next to x64, because jax
# fixes the cache at its first compile and code that jits before a
# runtime exists (kernels called directly, benches, tests) compiles
# before any engine object is built.  Where JAX_COMPILATION_CACHE_DIR is
# set jax reads it itself and the engine sets no directory; otherwise the
# cache is ONE fixed path inside the checkout — the path is part of the
# cache key's lookup, so a directory that moves with $HOME, a pid or a
# temporary name would never hit.
COMPILE_CACHE_DIR = os.environ.get("JAX_COMPILATION_CACHE_DIR")
if not COMPILE_CACHE_DIR:
    COMPILE_CACHE_DIR = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)  # failure is an error
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
# the engine's glue is many small programs; cache them all
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

__version__ = "0.1.0"

from blaze_tpu.schema import DataType, Field, Schema  # noqa: E402
from blaze_tpu.batch import ColumnBatch, DeviceColumn, HostColumn  # noqa: E402
from blaze_tpu.config import conf  # noqa: E402

__all__ = [
    "DataType",
    "Field",
    "Schema",
    "ColumnBatch",
    "DeviceColumn",
    "HostColumn",
    "conf",
    "COMPILE_CACHE_DIR",
    "__version__",
]

# count what JAX really compiles (eager glue included) from the first
# program on: bridge/xla_stats.py `backend_compiles`
from blaze_tpu.bridge import xla_stats as _xla_stats  # noqa: E402
_xla_stats.listen_backend_compiles()
