"""flow_pipeline_tpu — a TPU-native flow-analytics framework.

A brand-new framework with the capabilities of cloudflare/flow-pipeline
(flow generation/collection -> Kafka transport -> ingest -> windowed
aggregation -> dashboards), re-designed TPU-first: the aggregation tier is a
device-resident streaming-sketch engine (count-min, space-saving top-K,
EWMA/quantile anomaly detection) written in JAX, sharded over a
`jax.sharding.Mesh` with ICI collectives merging per-chip sketch state.

Module map (mirrors the reference's layer map, SURVEY.md §1):

- ``schema``     wire format + columnar batches     (ref: pb-ext/)
- ``gen``        synthetic flow generation          (ref: mocker/)
- ``transport``  partitioned bus w/ offsets         (ref: Kafka topic `flows`)
- ``models``     aggregation models: exact oracle,
                 count-min HH, space-saving, DDoS   (ref: ClickHouse flows_5m)
- ``ops``        TPU kernels: hashing, sketch
                 updates, segment reductions        (ref: none — the TPU substitution)
- ``engine``     streaming engine, windows, flush   (ref: inserter/ + Kafka engine)
- ``parallel``   mesh, shard_map, sketch allreduce  (ref: 2-partition consumer group)
- ``sink``       Postgres/ClickHouse row writers    (ref: compose/{postgres,clickhouse})
- ``obs``        metrics, logging, /metrics         (ref: Prometheus + logrus)
- ``utils``      dotted-flag config, misc           (ref: Go `flag`)
"""

__version__ = "0.1.0"
