"""Host dataplane runtime: everything between "decoded batch" and
"device step".

The host side of the pipeline had no runtime of its own — one thread
did grouping, the device step, window flushing and sink writes in
strict sequence, and grouping and flushing held most of its time. This package gives it one,
shaped like the partitioned pre-aggregation front-ends of the streaming
top-K literature (PAPERS.md: arxiv 2511.16797, 2504.16896 — a sharded
pre-aggregation stage FEEDING the sketch, never a global sort on the
hot path):

- ingest.shard     sharded grouping: hash-partitioned per-shard
                   group/sum on a persistent thread pool (numpy releases
                   the GIL), plus the native radix-group kernel switch.
- ingest.executor  pipelined stage graph decode -> group -> device step
                   with bounded queues, double buffering, backpressure
                   and a drain/stop protocol.
- ingest.flush     background flusher: top-K extraction and sink writes
                   for closed windows run off the hot path, with errors
                   propagated back to the worker.

engine.worker wires these in where engine.dataplane chose a pipeline
with the prepare/apply split (WorkerConfig.ingest_mode="serial" keeps
the single-threaded path, the parity reference); per-stage queue depths export through
obs.metrics as ingest_queue_depth / ingest_queue_highwater.
"""

from .executor import PipelinedExecutor
from .flush import AsyncFlusher, FlushError
from .shard import ShardPool, group_by_key_sharded

__all__ = [
    "AsyncFlusher",
    "FlushError",
    "PipelinedExecutor",
    "ShardPool",
    "group_by_key_sharded",
]
