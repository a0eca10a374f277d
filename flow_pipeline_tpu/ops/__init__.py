"""Device ops: the TPU compute kernels of the framework.

Everything here is jit-safe, static-shape, 32-bit-lane code. The design
replaces ClickHouse's C++ aggregation engine (the reference's only "native
kernel", ref: compose/clickhouse/create.sh:70-110) with XLA:

- ``segment``   sort-based exact groupby (lexicographic multi-key lax.sort
                + segment reductions) — the workhorse behind exact windowed
                aggregation and candidate extraction
- ``cms``       count-min sketch update/query/merge (+ conservative update)
- ``topk``      device-resident top-K candidate table (space-saving style
                merge with bounded error)
- ``ewma``      per-bucket EWMA for anomaly baselines
- ``quantile``  log-bucket histogram (DDSketch-flavored) quantiles
"""

from .segment import sort_groupby
from .cms import (
    cms_init,
    cms_add,
    cms_add_conservative,
    cms_query,
    cms_merge,
    cms_buckets,
)
from .topk import topk_init, topk_merge, topk_extract
from .ewma import ewma_init, ewma_fold, zscores, bucket_of, rate_accumulate
from .quantile import QuantileSketchSpec

__all__ = [
    "sort_groupby",
    "cms_init",
    "cms_add",
    "cms_add_conservative",
    "cms_query",
    "cms_merge",
    "cms_buckets",
    "topk_init",
    "topk_merge",
    "topk_extract",
    "ewma_init",
    "ewma_fold",
    "zscores",
    "bucket_of",
    "rate_accumulate",
    "QuantileSketchSpec",
]
