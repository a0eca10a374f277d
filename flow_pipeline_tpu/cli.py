"""Command-line entry points with the reference's dotted-flag surface.

Subcommands (``python -m flow_pipeline_tpu.cli <cmd> [-flags...]``):

- ``mocker``     synthetic flow producer (ref: mocker/mocker.go) — to a
                 frames file, or a real Kafka broker when a client exists.
- ``processor``  the TPU aggregation worker (the "new service" slot in the
                 reference architecture, ref: README.md:26-47) with
                 ``-processor.backend=tpu|cpu`` (BASELINE.json flag parity).
- ``inserter``   raw-row sink service (ref: inserter/inserter.go): consumes
                 flows and lands them in SQLite/Postgres unaggregated.
- ``pipeline``   single-process end-to-end demo: mocker -> in-process bus ->
                 processor -> sinks + /metrics, no external services.
"""

from __future__ import annotations

# flowlint: net-checked
# (the lineage subcommand fetches from a possibly-dead coordinator)

import sys
import time

from .obs import MetricsServer, get_logger, set_level
from .utils.flags import FlagSet

log = get_logger("cli")


def _common_flags(fs: FlagSet) -> FlagSet:
    fs.string("loglevel", "info", "Log level")
    fs.string("kafka.topic", "flows", "Bus topic to use")
    fs.string("kafka.brokers", "127.0.0.1:9092,[::1]:9092",
              "Kafka brokers list separated by commas")
    fs.boolean("proto.fixedlen", False, "Enable fixed length protobuf")
    return fs


def _gen_flags(fs: FlagSet) -> FlagSet:
    fs.integer("produce.count", 100_000, "Flows to generate (0 = endless)")
    fs.number("produce.rate", 100_000.0, "Modeled flows/sec for timestamps")
    fs.integer("produce.seed", 0, "Generator seed")
    fs.string("produce.profile", "mocker", "mocker | zipf")
    fs.integer("zipf.keys", 10_000, "Distinct keys in zipf mode")
    fs.number("zipf.alpha", 1.2, "Zipf exponent")
    fs.number("zipf.spread", 0.0,
              "Fraction of zipf-mode flows emitted by skewed-fan-out "
              "spreader/scanner legs (0 disables; exercises -spread.*)")
    fs.boolean("produce.shard", False,
               "Partition produced flows by 5-tuple KEY HASH over "
               "-bus.partitions partitions (the flowmesh shard "
               "contract) instead of round-robin")
    return fs


def _make_generator(vals):
    from .gen import FlowGenerator, MockerProfile, ZipfProfile

    profile = (
        ZipfProfile(n_keys=vals["zipf.keys"], alpha=vals["zipf.alpha"],
                    spread_fraction=vals["zipf.spread"])
        if vals["produce.profile"] == "zipf"
        else MockerProfile()
    )
    return FlowGenerator(profile, seed=vals["produce.seed"],
                         rate=vals["produce.rate"])


def _batch_frames(batch):
    """Per-frame bytes for a batch (bus produce needs one message per
    frame; file writers should use batch.to_wire() directly)."""
    from .schema import wire

    return wire.iter_raw_frames(batch.to_wire())


def mocker_main(argv=None) -> int:
    fs = _common_flags(FlagSet("mocker"))
    _gen_flags(fs)
    fs.string("out", "", "Write length-prefixed frames to this file instead "
                         "of Kafka")
    fs.integer("produce.batch", 4096, "Frames per write")
    fs.integer("bus.partitions", 2, "Topic partition count (the "
                                    "-produce.shard key-hash modulus)")
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    gen = _make_generator(vals)
    total = vals["produce.count"]
    from .schema import wire

    if vals["out"]:
        written = 0
        with open(vals["out"], "wb") as f:
            while total == 0 or written < total:
                n = min(vals["produce.batch"], total - written) if total else vals["produce.batch"]
                f.write(gen.batch(n).to_wire())
                written += n
                if total == 0 and written % (vals["produce.batch"] * 64) == 0:
                    log.info("produced %d frames", written)
        log.info("wrote %d frames to %s", written, vals["out"])
        return 0
    from .transport import kafka as tkafka

    if not tkafka.available():
        log.error("no Kafka client in this environment; use -out FILE "
                  "or the in-process `pipeline` command")
        return 2
    producer = tkafka.KafkaProducerAdapter(
        vals["kafka.brokers"], vals["kafka.topic"], vals["proto.fixedlen"]
    )
    sent = 0
    while total == 0 or sent < total:
        n = min(4096, total - sent) if total else 4096
        batch = gen.batch(n)
        if vals["produce.shard"]:
            # flowmesh shard contract: every row of a flow key lands on
            # the same partition (mesh/runtime.py shard_ids)
            from .mesh import shard_ids

            pids = shard_ids(batch, vals["bus.partitions"])
            for i, m in enumerate(batch.to_messages()):
                producer.send(m, partition=int(pids[i]))
        else:
            for m in batch.to_messages():
                producer.send(m)
        sent += n
    producer.flush()
    log.info("produced %d flows to %s", sent, vals["kafka.topic"])
    return 0


def _build_models(vals):
    from .engine import WindowedHeavyHitter
    from .models import (
        DDoSConfig,
        DDoSDetector,
        HeavyHitterConfig,
        WindowAggConfig,
        WindowAggregator,
    )

    batch = vals["processor.batch"]
    n_mesh = vals.get("processor.mesh", 0)
    mesh = None
    if n_mesh:
        from .parallel import make_mesh

        mesh = make_mesh(n_mesh)
    # -window.slide: the ranked tables slide (engine/windowed.py); the
    # ring holds single-chip device states, so refuse loudly where the
    # states are sharded replicas or live in the host engine
    slide = vals.get("window.slide", 0)
    if slide:
        from .models.oracle import SECONDS_PER_SLOT

        if slide < 0 or SECONDS_PER_SLOT % slide:
            raise ValueError(
                f"-window.slide must divide the {SECONDS_PER_SLOT} s "
                f"window, got {slide}")
        for flag, on, why in (
                ("-processor.mesh", n_mesh,
                 "per-chip replicas of every sketch"),
                ("-sketch.backend host",
                 vals.get("sketch.backend", "device") == "host",
                 "sketch state resident in the host engine"),
                ("-spread.enabled", vals.get("spread.enabled"),
                 "the register planes have no fold program for the "
                 "ring to run"),
                ("-mesh.role", vals.get("mesh.role"),
                 "windows merged and extracted at the coordinator")):
            if on:
                raise ValueError(
                    f"-window.slide does not support {flag} ({why}): "
                    f"the ring of sub-window states is a single chip's "
                    f"device state")

    # -window.lateness reaches every windowed family: flows_5m emits
    # late partials, a ranked table and the detector hold the unit that
    # rolled open for that long (models/held.py). Where a family cannot,
    # say so here in words and run it at 0 (it drops and counts, as
    # before): no path changes silently. The mesh honours it; the
    # host-grouped dataplanes are the worker's to name (it picks them).
    lateness = vals["window.lateness"]
    if lateness < 0:
        raise ValueError(f"-window.lateness must be >= 0, got {lateness}")
    held = lateness
    for flag, on, why in (
            ("-window.slide", slide,
             "a closed sub-window is in the ring, folded and written"),
            ("-sketch.backend host",
             vals.get("sketch.backend", "device") == "host",
             "sketch state resident in the host engine"),
            ("-mesh.role", vals.get("mesh.role"),
             "a member ships a window's state at the roll and the "
             "coordinator merges it once")):
        if lateness and on:
            log.warning(
                "-window.lateness %d: under %s the ranked tables and the "
                "detector still drop the rows that arrive after their "
                "unit rolled, and count them in late_flows_dropped (%s); "
                "flows_5m honours it", lateness, flag, why)
            held = 0

    models = {}
    if vals["model.flows5m"]:
        cfg = WindowAggConfig(batch_size=batch,
                              allowed_lateness=lateness)
        if mesh:
            from .parallel import ShardedWindowAggregator

            models["flows_5m"] = ShardedWindowAggregator(
                cfg, mesh, name="flows_5m")
        else:
            models["flows_5m"] = WindowAggregator(cfg)
    # -hh.sketch=auto (the r19 default): CASCADE families — those whose
    # key set is a strict subset of another enabled hh family's (the
    # exact condition engine/hostfused.py _fam_plan regroups on; cli hh
    # families share value/scale columns by construction) — default to
    # the invertible sketch: their decode sets are small (a src/dst-IP
    # family groups 3-4x under its 5-tuple parent, far below the
    # depth*width peel budget) and the admission machinery they'd
    # otherwise pay is pure hot-path cost, which the invertible sketch
    # does not have. ROOT families keep the table sketch. The flip engages only where the invertible family can
    # actually serve: the host sketch dataplane, no device mesh —
    # elsewhere auto means table, so a default worker never degrades to
    # the per-model numpy path. -hh.sketch=table|invertible overrides
    # every family, exactly as before.
    hh_families = []
    if vals["model.talkers"]:
        hh_families.append(("top_talkers",
                            ("src_addr", "dst_addr", "src_port",
                             "dst_port", "proto")))
    if vals["model.pairs"]:
        # BASELINE config 2: (SrcAddr, DstAddr) heavy hitters, the key
        # HeavyHitterConfig defaults to. A prefix of the 5-tuple and an
        # extension of src_addr, so in the fused step it rides their
        # sort as a third member (engine/fused.py: chains)
        hh_families.append(("top_pairs", ("src_addr", "dst_addr")))
    if vals["model.ips"]:
        hh_families.append(("top_src_ips", ("src_addr",)))
        hh_families.append(("top_dst_ips", ("dst_addr",)))

    def resolve_hh_sketch(key_cols) -> str:
        mode = vals.get("hh.sketch", "auto")
        if mode != "auto":
            return mode
        from .engine.dataplane import host_sketch_serves

        # only the host sketch pipeline folds an invertible family, and
        # a mesh's models never get it: elsewhere it would land on the
        # slow per-model numpy path, exactly what auto must never choose
        if mesh or not host_sketch_serves(
                vals.get("processor.fused", True),
                vals.get("sketch.backend", "device"),
                vals.get("processor.hostassist", "auto")):
            return "table"
        cascade = any(set(key_cols) < set(other)
                      for _, other in hh_families)
        return "invertible" if cascade else "table"

    def windowed_hh(name, key_cols):
        cfg = HeavyHitterConfig(
            key_cols=key_cols,
            batch_size=batch,
            width=vals["sketch.width"],
            capacity=vals["sketch.capacity"],
            table_prefilter=vals["sketch.prefilter"],
            table_admission=vals["sketch.admission"],
            hh_sketch=resolve_hh_sketch(key_cols),
        )
        if mesh:
            if cfg.hh_sketch == "invertible":
                # ShardedHeavyHitter shards the jitted table step over a
                # device mesh; the invertible family's exact u64 planes
                # have no device layout to shard — refuse instead of
                # silently running the wrong family
                raise ValueError(
                    "-hh.sketch=invertible does not support "
                    "-processor.mesh device sharding (host-resident "
                    "u64 planes); use flowmesh workers instead")
            from .parallel import ShardedHeavyHitter

            return WindowedHeavyHitter(cfg, k=vals["sketch.topk"],
                                       model_cls=ShardedHeavyHitter,
                                       lateness=held, mesh=mesh, name=name)
        return WindowedHeavyHitter(cfg, k=vals["sketch.topk"],
                                   slide_seconds=slide, slide_name=name,
                                   lateness=held)

    # top_talkers (5-tuple) + top src/dst IP tables (ref: viz.json "Top
    # source/destination IPs"; per-address windowed HH, one per
    # direction) — the set collected above so auto sketch resolution
    # sees every family before any is built.
    for name, key_cols in hh_families:
        models[name] = windowed_hh(name, key_cols)
    if vals["model.ports"]:
        # Top src/dst port tables (ref: viz.json top port panels). The
        # 2^16 port space fits a dense EXACT accumulator — one segment
        # add per batch, no sketch error, top-K is one lax.top_k
        # (models.dense_top) — under the same window lifecycle.
        from .models import DenseTopConfig, DenseTopKModel

        for col, name in (("src_port", "top_src_ports"),
                          ("dst_port", "top_dst_ports")):
            cfg = DenseTopConfig(key_col=col, batch_size=batch)
            if mesh:
                from .parallel import ShardedDenseTopK

                models[name] = WindowedHeavyHitter(
                    cfg, k=vals["sketch.topk"],
                    model_cls=ShardedDenseTopK, lateness=held,
                    mesh=mesh, name=name,
                )
            else:
                models[name] = WindowedHeavyHitter(
                    cfg, k=vals["sketch.topk"], model_cls=DenseTopKModel,
                    slide_seconds=slide, slide_name=name, lateness=held,
                )
    if vals["model.ddos"]:
        if mesh:
            from .parallel import ShardedDDoSDetector

            models["ddos_alerts"] = ShardedDDoSDetector(
                DDoSConfig(batch_size=batch), mesh, name="ddos_alerts",
                lateness=held,
            )
        else:
            models["ddos_alerts"] = DDoSDetector(
                DDoSConfig(batch_size=batch), lateness=held)
    if vals.get("spread.enabled"):
        # flowspread distinct-count detectors (models/superspreader.py,
        # models/scan.py). Where their state lives is the dataplane's
        # choice (engine/dataplane.py): on one chip the fused step
        # updates the register planes on the device, the host-grouped
        # pipelines keep them in host numpy. Neither form has a sharded
        # layout yet (parallel/sharded.py knows no spread kind), so
        # refuse -processor.mesh instead of silently running an
        # unsharded model beside sharded ones.
        if mesh:
            raise ValueError(
                "-spread.enabled does not support -processor.mesh device "
                "sharding (the register planes have one-chip and host "
                "layouts only, no sharded one); use flowmesh workers "
                "instead")
        from .models.scan import SCAN_MODEL, scan_config, scan_model
        from .models.superspreader import (
            SUPERSPREADER_MODEL,
            superspreader_config,
            superspreader_model,
        )

        sizing = dict(depth=vals["spread.depth"], width=vals["spread.width"],
                      registers=vals["spread.regs"],
                      capacity=vals["spread.capacity"], batch_size=batch)
        models[SUPERSPREADER_MODEL] = superspreader_model(
            superspreader_config(**sizing), k=vals["spread.topk"],
            lateness=held)
        models[SCAN_MODEL] = scan_model(
            scan_config(**sizing), k=vals["spread.topk"], lateness=held)
    return models


def _processor_flags(fs: FlagSet) -> FlagSet:
    fs.string("processor.backend", "tpu",
              "tpu (required: exits non-zero unless jax.devices()[0] is "
              "a TPU; JAX_PLATFORMS=cpu overrides) | cpu (pins the CPU)")
    fs.integer("processor.batch", 32768, "Device batch rows (per chip)")
    fs.integer("processor.mesh", 0, "Shard models over this many devices "
                                    "(0 = single chip)")
    fs.boolean("processor.fused", True, "One fused device step per batch "
                                        "with shared pre-aggregation")
    fs.string("processor.hostassist", "auto",
              "Host-grouped pre-aggregation: auto (CPU backend only) "
              "| on | off")
    fs.boolean("model.flows5m", True, "Exact 5m rollup model")
    fs.boolean("model.talkers", True, "5-tuple top-K talkers model")
    fs.boolean("model.pairs", False,
               "Host-pair (src_addr, dst_addr) top-K model: table "
               "top_pairs")
    fs.boolean("model.ips", True, "Top src/dst IP models")
    fs.boolean("model.ports", True, "Top src/dst port models")
    fs.boolean("model.ddos", True, "DDoS spike detector")
    fs.integer("sketch.width", 1 << 16, "Count-min width")
    fs.string("sketch.backend", "device",
              "Sketch step executor: device (jitted CMS/top-K apply) | "
              "host (native threaded uint64 engine; needs the "
              "host-grouped pipeline)")
    fs.string("hh.sketch", "auto",
              "Heavy-hitter sketch family: auto (cascade families — "
              "key sets that are strict subsets of another hh family's "
              "— run invertible when the host sketch dataplane serves "
              "and no device mesh is configured; root families and "
              "every other deployment keep table) | table (CMS + top-K "
              "admission table — prefilter, admission CMS queries, "
              "table merge) | invertible (linear key-recovery sketch: "
              "no admission machinery on the hot path, heavy keys "
              "decoded from the sketch at window close, mesh merge a "
              "plain u64 sum; ignores -sketch.prefilter/-sketch."
              "admission and forces the plain CMS update; wants "
              "-sketch.backend=host)")
    fs.boolean("spread.enabled", False,
               "flowspread distinct-count detectors: superspreaders "
               "(src -> distinct dst addrs) + portscan (src -> distinct "
               "dst ports); register planes on the device inside the "
               "fused step, in host memory on the host-grouped "
               "dataplanes; incompatible with -processor.mesh")
    fs.integer("spread.depth", 2, "Spread sketch rows (min over rows at "
                                  "decode)")
    fs.integer("spread.width", 1 << 12, "Spread sketch buckets per row")
    fs.integer("spread.regs", 64, "u8 registers per spread bucket "
                                  "(~1.04/sqrt(m) rel err past the "
                                  "linear-counting regime)")
    fs.integer("spread.capacity", 512, "Spread candidate-table capacity")
    fs.integer("spread.topk", 64, "Spread rows emitted per window")
    fs.string("sketch.admission", "est",
              "Top-K table admission: est (space-saving, CMS-seeded) | "
              "plain (batch-sum merge; benchmarking A/B only)")
    fs.boolean("sketch.prefilter", True, "Pre-truncate table-merge "
                                         "candidates to top-capacity")
    fs.integer("sketch.capacity", 1024, "Top-K table capacity")
    fs.integer("sketch.topk", 100, "Rows emitted per window")
    fs.integer("window.lateness", 0, "Allowed lateness seconds")
    fs.integer("window.slide", 0,
               "Slide of the ranked tables in seconds, a divisor of the "
               "300 s window: every table's rows for the last 300 s are "
               "emitted at each slide end from a ring of 300/slide "
               "sub-window sketch states on the device (flows_5m and "
               "ddos_alerts are unchanged). 0 = tumbling windows. Not "
               "with -processor.mesh, -sketch.backend host, "
               "-spread.enabled or -mesh.role: refused at start-up")
    fs.boolean("archive.raw", False, "Archive full-fidelity rows to "
                                     "flows_raw on sinks that support it")
    fs.integer("feed.prefetch", 2, "Decoded batches fetched ahead of the "
                                   "device step (0 disables)")
    fs.integer("ingest.threads", 0,
               "Worker threads inside the native dataplane kernels "
               "(fused pass, sketch engine, lane building, wagg fold); "
               "deterministic at any count — 0 keeps the conservative "
               "auto count (half the cores, capped at 4)")
    fs.string("ingest.fused", "auto",
              "Single-pass fused native dataplane (group->cascade->"
              "sketch in one C pass): auto (on when sketch.backend=host "
              "and libflowdecode exports it) | on (required — errors "
              "when it cannot serve) | off (staged parity reference)")
    fs.string("checkpoint.path", "", "Snapshot directory")
    fs.integer("flush.count", 50, "Batches between snapshots")
    fs.string("metrics.addr", "127.0.0.1:8081", "host:port for /metrics "
                                                "(empty disables)")
    fs.string("obs.trace", "ring",
              "flowtrace per-chunk span recorder: ring (flight recorder, "
              "<2% overhead — dump via /debug/trace or on worker error) "
              "| always (retain every span; CI/diagnostics only) | off")
    fs.string("obs.audit", "sample",
              "sketchwatch sampled exact shadow audit (sketch accuracy "
              "observability): sample (deterministic ~1/256 key cohort, "
              "<2% overhead — error/recall/saturation metrics per "
              "window close, /query/audit on flowserve) | full (every "
              "key; tests and sweeps) | off")
    fs.string("sink", "stdout", "stdout | sqlite:PATH | postgres:DSN | "
                                "clickhouse:URL (comma separated)")
    # flowchaos (utils/faults.py, sink/resilient.py, mesh/journal.py):
    # fault injection + retry/dead-letter + coordinator durability —
    # see docs/FAULT_TOLERANCE.md
    fs.string("faults", "", "flowchaos deterministic fault plan, e.g. "
                            "'sink.write:p=0.05;mesh.submit:p=0.02"
                            "@seed=7' (empty disables; seams cost one "
                            "attribute read when off). delay=<s> makes "
                            "a site inject LATENCY instead of failure — "
                            "'sink.write:delay=0.02' stalls every "
                            "write, 'bus.poll:p=0.5:delay=0.1' stalls "
                            "half — the slow-dependency overload shape "
                            "flowguard degrades under",
              env="FLOWTPU_FAULTS")
    # flowguard (guard/): end-to-end overload control — bounded-buffer
    # backpressure, the watermark-lag degradation ladder, read-side
    # admission — see docs/FAULT_TOLERANCE.md "flowguard"
    fs.number("guard.lag", 0.0,
              "flowguard watermark-lag budget in seconds before the "
              "degradation ladder engages: level 1 drops optional work "
              "(audit cohort refresh, trace ring), levels >=2 are "
              "deterministic hash-sampled admission at keep rate "
              "1/2^(level-1) with unbiased scaled estimates; recovery "
              "steps back up with hysteresis (0 = disarmed, the exact "
              "default)")
    fs.integer("guard.max_level", 6,
               "flowguard ladder ceiling (6 = keep rate 1/32 at full "
               "degradation)")
    fs.integer("guard.serve_queue", 0,
               "flowserve read-side admission: max concurrently "
               "computing queries; past it + the deadline, 503 with "
               "Retry-After (0 = unbounded, the default)")
    fs.number("guard.serve_deadline", 0.1,
              "flowserve admission deadline seconds a query may wait "
              "for a compute slot before it is shed with 503")
    fs.integer("sink.retries", 4, "Sink write attempts before a batch "
                                  "is dead-lettered (with "
                                  "-sink.deadletter) or the step fails "
                                  "(without); 1 disables retries")
    fs.string("sink.deadletter", "", "Directory for the replayable "
                                     "dead-letter spill (<dir>/"
                                     "deadletter/); batches that "
                                     "exhaust retries land here "
                                     "instead of crashing the worker; "
                                     "re-ingest with flowtpu-replay "
                                     "(empty = fail the step, the "
                                     "crash-and-replay contract)")
    fs.string("mesh.journal", "", "Coordinator write-ahead journal "
                                  "directory (mesh.role=coordinator): "
                                  "accepted submissions, fences, epoch "
                                  "bumps and merged-window keys become "
                                  "durable; a restarted coordinator "
                                  "recovers its frontier/epoch/ledger "
                                  "(empty = in-memory only)")
    # flowmesh (mesh/): N-worker sharded sketch mesh with window-close
    # merge and live rebalance — see docs/ARCHITECTURE.md "flowmesh"
    fs.integer("mesh.workers", 0, "Run an in-process flowmesh of this "
                                  "many workers (pipeline command; "
                                  "0 disables)")
    fs.string("mesh.role", "", "flowmesh role: coordinator | member "
                               "(processor command; empty = standalone)")
    fs.string("mesh.coordinator", "", "flowmesh coordinator base URL "
                                      "(member role), e.g. "
                                      "http://coordinator:8090")
    fs.string("mesh.id", "", "flowmesh member id (default host-pid)")
    fs.string("mesh.listen", "", "flowmesh listen host:port — the "
                                 "coordinator's protocol/query HTTP "
                                 "(default :8090), or the member's "
                                 "state endpoint for /topk fan-out "
                                 "(empty disables)")
    fs.number("mesh.heartbeat", 5.0, "flowmesh heartbeat timeout "
                                     "seconds before a member is fenced")
    fs.integer("bus.partitions", 2, "Bus partitions (reference default "
                                    "2; the mesh coordinator's "
                                    "partition-count contract)")
    fs.string("in", "", "Read frames from file instead of Kafka")
    fs.string("listen.feed", "", "gRPC feed address (host:port) — accept "
                                 "batches from colocated producers instead "
                                 "of Kafka")
    fs.string("query.addr", "", "Live query API host:port (O(K) top-K / "
                                "open windows / alerts; empty disables)")
    # flowserve (serve/): lock-free snapshot read serving — see
    # docs/ARCHITECTURE.md "flowserve"
    fs.string("serve.addr", "", "flowserve query host:port (/query/topk, "
                                "/query/estimate, /query/range off "
                                "versioned immutable snapshots — readers "
                                "never touch the dataplane locks; empty "
                                "disables)")
    fs.number("serve.refresh", 2.0, "flowserve open-window snapshot "
                                    "refresh cadence in seconds "
                                    "(snapshots always publish at window "
                                    "close; 0 = window-close only)")
    fs.integer("serve.feed_bytes", 0,
               "flowserve subscription-feed delta-chain byte budget; "
               "subscribers further behind than the retained chain "
               "take a full resync (0 = library default, 128 MiB)")
    return fs


def _apply_backend(backend: str) -> None:
    """utils.platform's one rule — an explicit CPU request pins the CPU,
    anything else requires a TPU or exits — which also places the
    compile cache before the first compile."""
    from .utils.platform import select_platform

    log.info("platform=%s", select_platform(backend))


def _pg_dsn(dsn: str) -> str:
    """Apply the $POSTGRES_PASSWORD fallback when the DSN has no password
    (the reference's env fallback, ref: inserter/inserter.go:220-224)."""
    import os

    password = os.environ.get("POSTGRES_PASSWORD")
    if password and "password" not in dsn:
        dsn = f"{dsn} password={password}"
    return dsn


def _make_sinks(spec: str, retries: int = 0, deadletter: str = ""):
    from .sink import (ClickHouseSink, PostgresSink, ResilientSink,
                       SQLiteSink, StdoutSink)

    sinks = []
    for part in filter(None, spec.split(",")):
        kind, _, arg = part.partition(":")
        if kind == "stdout":
            sinks.append(StdoutSink())
        elif kind == "sqlite":
            sinks.append(SQLiteSink(arg or ":memory:"))
        elif kind == "postgres":
            sinks.append(PostgresSink(_pg_dsn(arg)))
        elif kind == "clickhouse":
            sinks.append(ClickHouseSink(arg or "http://localhost:8123"))
        else:
            raise ValueError(f"unknown sink {part!r}")
    if retries > 1 or deadletter:
        # flowchaos: bounded backoff + (optionally) the replayable
        # dead-letter spill around every configured sink edge
        sinks = [ResilientSink(s, retries=max(1, retries),
                               deadletter_dir=deadletter or None)
                 for s in sinks]
    return sinks


def _vals_sinks(vals):
    """The flag-configured sink stack (shared by every service main)."""
    return _make_sinks(vals["sink"], retries=vals["sink.retries"],
                       deadletter=vals["sink.deadletter"])


def _host_port(addr: str, default_port: int,
               default_host: str = "127.0.0.1") -> tuple[str, int]:
    """Parse "host:port" / ":port" / "host" / "port" with clear errors —
    the single address parser for every listen-style flag."""
    host, sep, port = addr.rpartition(":")
    if not sep:  # no colon: bare port number or bare hostname
        if addr.isdigit():
            host, port = "", addr
        else:
            host, port = addr, ""
    if port and not port.isdigit():
        raise ValueError(f"invalid port in address {addr!r}")
    return host or default_host, int(port) if port else default_port


def _start_metrics(addr: str, default_port: int):
    """host:port -> started MetricsServer, or None when addr is empty."""
    if not addr:
        return None
    host, port = _host_port(addr, default_port)
    server = MetricsServer(port, host=host).start()
    log.info("metrics on http://%s:%d/metrics", host, server.port)
    return server


def _load_frames_bus(path: str, topic: str, partitions: int = 2):
    """Preload a frames file onto an in-process bus (the -in path). Frames
    are split by scanning length prefixes and produced as raw bytes — the
    single protobuf decode happens downstream in the consumer."""
    from .schema import wire
    from .transport import InProcessBus

    bus = InProcessBus()
    bus.create_topic(topic, partitions)
    with open(path, "rb") as f:
        data = f.read()
    bus.produce_many(topic, wire.iter_raw_frames(data))
    return bus


def _worker_config(vals) -> "WorkerConfig":
    from .engine import WorkerConfig

    return WorkerConfig(
        # -processor.batch is rows PER CHIP: a mesh of N takes N x that
        # per poll, or the row-sharded global batch hands every real row
        # to chip 0 and padding to the rest
        poll_max=vals["processor.batch"] * max(
            1, vals.get("processor.mesh", 0)),
        snapshot_every=vals["flush.count"],
        checkpoint_path=vals["checkpoint.path"] or None,
        archive_raw=vals["archive.raw"],
        prefetch=vals["feed.prefetch"],
        fused=vals["processor.fused"],
        host_assist=vals["processor.hostassist"],
        sketch_backend=vals["sketch.backend"],
        ingest_threads=vals["ingest.threads"],
        # the native radix grouping falls back to numpy when unbuilt
        ingest_native_group=True,
        ingest_fused=vals["ingest.fused"],
        obs_audit=vals["obs.audit"],
        guard_lag=vals["guard.lag"],
        guard_max_level=vals["guard.max_level"],
    )


def _start_serve_worker(vals, worker):
    """Wire flowserve onto a standalone worker when -serve.addr is set:
    publisher into the batch loop + range-ledger sink, HTTP reader on
    the requested address. Returns (server, store) or (None, None)."""
    if not vals["serve.addr"]:
        return None, None
    from .serve import ServeServer, attach_worker

    pub = attach_worker(worker, refresh=vals["serve.refresh"])
    host, port = _host_port(vals["serve.addr"], 8083)
    server = ServeServer(
        pub.store, port, host,
        max_inflight=vals["guard.serve_queue"],
        deadline=vals["guard.serve_deadline"],
        feed_bytes=vals["serve.feed_bytes"],
    ).set_guard(worker.guard).start()
    return server, pub.store


def _start_serve_mesh(vals, coordinator):
    """Wire flowserve onto a mesh coordinator when -serve.addr is set:
    merged-view publisher thread + HTTP reader. Returns (server,
    publisher) or (None, None)."""
    if not vals["serve.addr"]:
        return None, None
    from .serve import ServeServer, attach_mesh

    pub = attach_mesh(coordinator, refresh=vals["serve.refresh"])
    host, port = _host_port(vals["serve.addr"], 8083)
    server = ServeServer(
        pub.store, port, host,
        max_inflight=vals["guard.serve_queue"],
        deadline=vals["guard.serve_deadline"],
        feed_bytes=vals["serve.feed_bytes"]).start()
    return server, pub


def _mesh_coordinator_main(vals) -> int:
    """flowmesh coordinator service: membership + merge barrier + the
    mesh-aware query surface. Consumes nothing itself."""
    from .engine.query_api import QueryServer
    from .mesh import MeshCoordinator, MeshCoordinatorServer, \
        spec_from_models

    specs = spec_from_models(_build_models(vals))
    coord = MeshCoordinator(specs, vals["bus.partitions"],
                            sinks=_vals_sinks(vals),
                            heartbeat_timeout=vals["mesh.heartbeat"],
                            journal=vals["mesh.journal"] or None)
    serve_srv, serve_pub = _start_serve_mesh(vals, coord)
    host, port = _host_port(vals["mesh.listen"] or ":8090", 8090,
                            default_host="0.0.0.0")
    server = MeshCoordinatorServer(coord, port, host).start()
    metrics = _start_metrics(vals["metrics.addr"], 8081)
    query = None
    if vals["query.addr"]:
        qhost, qport = _host_port(vals["query.addr"], 8082)
        query = QueryServer(None, qport, qhost, mesh=coord).start()
    log.info("mesh coordinator: %d partitions, models=%s",
             vals["bus.partitions"], [s.name for s in specs])
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        if query:
            query.stop()
        if serve_pub:
            serve_pub.stop()
        if serve_srv:
            serve_srv.stop()
        server.stop()
        if metrics:
            metrics.stop()
        coord.close()  # final journal fsync + file close
    return 0


def _mesh_member_main(vals) -> int:
    """flowmesh member service: a coordinator-driven StreamWorker over
    explicitly assigned Kafka partitions."""
    import os
    import socket

    from .mesh import MeshMember, MemberStateServer, RemoteCoordinator
    from .transport import kafka as tkafka

    if not vals["mesh.coordinator"]:
        log.error("mesh.role=member needs -mesh.coordinator URL")
        return 2
    if not tkafka.available():
        log.error("mesh member mode needs a Kafka client (the mesh "
                  "shards a real partitioned topic); use `pipeline "
                  "-mesh.workers N` for the in-process mesh")
        return 2
    member_id = vals["mesh.id"] or f"{socket.gethostname()}-{os.getpid()}"

    def consumer_factory(partitions):
        return tkafka.KafkaConsumerAdapter(
            vals["kafka.brokers"], vals["kafka.topic"],
            group=f"mesh-{member_id}", fixedlen=vals["proto.fixedlen"],
            partitions=list(partitions))

    state_url = None
    shost = sport = None
    if vals["mesh.listen"]:
        # the state endpoint port must be known before join() advertises
        # it; an explicit port keeps the advertised URL stable
        shost, sport = _host_port(vals["mesh.listen"], 8091,
                                  default_host="0.0.0.0")
        state_url = f"http://{socket.gethostname()}:{sport}/meshstate"
    trace_url = None
    if vals["metrics.addr"]:
        # meshscope: advertise this member's flight recorder so the
        # coordinator's /debug/trace can aggregate one clock-aligned
        # mesh-wide trace (the metrics server owns /debug/trace)
        _, mport = _host_port(vals["metrics.addr"], 8081)
        trace_url = f"http://{socket.gethostname()}:{mport}/debug/trace"
    coord = RemoteCoordinator(vals["mesh.coordinator"],
                              state_url=state_url, trace_url=trace_url)
    member = MeshMember(
        member_id, coord, consumer_factory,
        model_factory=lambda: _build_models(vals),
        config=_worker_config(vals),
        sinks=_vals_sinks(vals),
        # progress carries every 64 batches: bounds a successor's replay
        # (and the promotable carry) mid-window — windows are minutes of
        # stream, a rebalance should not replay minutes of flows
        submit_every=64, sync_interval=1.0, trace_url=trace_url)
    state = None
    if sport is not None:
        state = MemberStateServer(member, sport, shost).start()
    metrics = _start_metrics(vals["metrics.addr"], 8081)
    log.info("mesh member %s -> %s", member_id, vals["mesh.coordinator"])
    try:
        while True:
            if not member.step():
                time.sleep(0.05)
    except KeyboardInterrupt:
        log.info("interrupt: final submit + leave")
        member.finalize()
    finally:
        if state is not None:
            state.stop()
        if metrics:
            metrics.stop()
    return 0


def processor_main(argv=None) -> int:
    fs = _processor_flags(_common_flags(FlagSet("processor")))
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    from .obs.trace import TRACER
    from .utils.faults import FAULTS

    TRACER.configure(vals["obs.trace"])
    FAULTS.configure(vals["faults"])
    _apply_backend(vals["processor.backend"])
    if vals["mesh.role"]:
        if vals["mesh.role"] == "coordinator":
            return _mesh_coordinator_main(vals)
        if vals["mesh.role"] == "member":
            return _mesh_member_main(vals)
        raise ValueError(
            f"mesh.role must be coordinator|member, got "
            f"{vals['mesh.role']!r}")
    from .engine import StreamWorker, WorkerConfig
    from .transport import Consumer

    feed = None
    server = None
    query = None
    serve_srv = None
    try:
        if vals["in"]:
            bus = _load_frames_bus(vals["in"], vals["kafka.topic"])
            consumer = Consumer(bus, vals["kafka.topic"], fixedlen=True)
            stop_when_idle = True
        elif vals["listen.feed"]:
            from .transport import InProcessBus
            from .transport.feed import FeedServer

            bus = InProcessBus()
            feed = FeedServer(bus, vals["kafka.topic"],
                              vals["listen.feed"]).start()
            consumer = Consumer(bus, vals["kafka.topic"], fixedlen=True)
            stop_when_idle = False
        else:
            from .transport import kafka as tkafka

            if not tkafka.available():
                log.error("no Kafka client; use -in FILE, -listen.feed, or "
                          "`pipeline`")
                return 2
            consumer = tkafka.KafkaConsumerAdapter(
                vals["kafka.brokers"], vals["kafka.topic"],
                fixedlen=vals["proto.fixedlen"],
            )
            stop_when_idle = False
        server = _start_metrics(vals["metrics.addr"], 8081)
        worker = StreamWorker(
            consumer,
            _build_models(vals),
            _vals_sinks(vals),
            _worker_config(vals),
        )
        serve_srv, serve_store = _start_serve_worker(vals, worker)
        if vals["query.addr"]:
            from .engine.query_api import QueryServer

            qhost, qport = _host_port(vals["query.addr"], 8082)
            query = QueryServer(worker, qport, qhost,
                                serve=serve_store).start()
        if vals["checkpoint.path"]:
            if worker.restore():
                log.info("restored checkpoint from %s",
                         vals["checkpoint.path"])
        try:
            worker.run(stop_when_idle=stop_when_idle)
        except KeyboardInterrupt:
            log.info("interrupt: draining")
            worker.finalize()
    finally:
        # covers setup failures after feed/metrics start (bad sink, restore
        # error), not just the run loop
        if query:
            query.stop()
        if serve_srv:
            serve_srv.stop()
        if feed:
            feed.stop()
        if server:
            server.stop()
    log.info("processed %d flows in %d batches",
             worker.flows_seen, worker.batches_seen)
    return 0


def inserter_main(argv=None) -> int:
    """Raw-row sink service (reference inserter parity, ref:
    inserter/inserter.go): flows land unaggregated in the `flows` table."""
    fs = _common_flags(FlagSet("inserter"))
    fs.string("in", "", "Read frames from file instead of Kafka")
    fs.string("postgres.dsn", "", "Postgres DSN (enables PostgresSink)")
    fs.string("postgres.pass", "", "Postgres password", )
    fs.string("sqlite", "", "SQLite path (default sink)")
    fs.integer("flush.count", 100, "Rows per flush")
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    from .sink import PostgresSink, SQLiteSink

    if vals["postgres.dsn"]:
        dsn = vals["postgres.dsn"]
        if vals["postgres.pass"] and "password" not in dsn:
            dsn += f" password={vals['postgres.pass']}"
        sink = PostgresSink(_pg_dsn(dsn))
    else:
        sink = SQLiteSink(vals["sqlite"] or ":memory:")
    if vals["in"]:
        bus = _load_frames_bus(vals["in"], vals["kafka.topic"])
        from .transport import Consumer

        consumer = Consumer(bus, vals["kafka.topic"],
                            group="postgres-inserter", fixedlen=True)
        stop_when_idle = True
    else:
        from .transport import kafka as tkafka

        if not tkafka.available():
            log.error("no Kafka client in this environment; use -in FILE")
            return 2
        consumer = tkafka.KafkaConsumerAdapter(
            vals["kafka.brokers"], vals["kafka.topic"],
            group="postgres-inserter", fixedlen=vals["proto.fixedlen"],
        )
        stop_when_idle = False
    total = 0
    try:
        while True:
            batch = consumer.poll(vals["flush.count"])
            if batch is None:
                if stop_when_idle:
                    break
                time.sleep(0.05)
                continue
            sink.write("flows", _raw_rows(batch))
            consumer.commit(batch.partition, batch.last_offset + 1)
            total += len(batch)
    except KeyboardInterrupt:
        pass
    log.info("inserted %d raw rows", total)
    return 0


def _raw_rows(batch) -> list[dict]:
    from .sink.base import _addr_str

    import datetime

    c = batch.columns
    return [
        {
            # TIMESTAMP columns (Postgres) need a timestamp, not epoch int
            "time_flow": datetime.datetime.fromtimestamp(
                int(c["time_received"][i]), datetime.timezone.utc
            ).strftime("%Y-%m-%d %H:%M:%S"),
            "type": int(c["type"][i]),
            "sampling_rate": int(c["sampling_rate"][i]),
            "src_as": int(c["src_as"][i]),
            "dst_as": int(c["dst_as"][i]),
            "src_ip": _addr_str(c["src_addr"][i]),
            "dst_ip": _addr_str(c["dst_addr"][i]),
            "bytes": int(c["bytes"][i]),
            "packets": int(c["packets"][i]),
            "etype": int(c["etype"][i]),
            "proto": int(c["proto"][i]),
            "src_port": int(c["src_port"][i]),
            "dst_port": int(c["dst_port"][i]),
        }
        for i in range(len(batch))
    ]


def _pipeline_mesh(vals) -> int:
    """In-process flowmesh run (`pipeline -mesh.workers N`): key-hash
    sharded produce -> N coordinator-driven workers -> network-wide
    window merge at close."""
    from .engine.query_api import QueryServer
    from .mesh import InProcessMesh, produce_sharded
    from .transport import InProcessBus

    if vals.get("processor.mesh"):
        raise ValueError(
            "-mesh.workers is the horizontal (multi-worker) scale-out; "
            "combining it with -processor.mesh device sharding inside "
            "each member is not supported yet")
    n_workers = vals["mesh.workers"]
    partitions = max(vals["bus.partitions"], n_workers)
    bus = InProcessBus()
    bus.create_topic(vals["kafka.topic"], partitions)
    gen = _make_generator(vals)
    t0 = time.perf_counter()
    produced = 0
    while produced < vals["produce.count"]:
        n = min(8192, vals["produce.count"] - produced)
        produced += produce_sharded(bus, vals["kafka.topic"],
                                    gen.batch(n), partitions)
    log.info("produced %d flows (key-hash sharded over %d partitions) "
             "in %.2fs", produced, partitions, time.perf_counter() - t0)
    sinks = _vals_sinks(vals)
    server = _start_metrics(vals["metrics.addr"], 8081)
    mesh = InProcessMesh(
        bus, vals["kafka.topic"], n_workers,
        model_factory=lambda: _build_models(vals),
        config=_worker_config(vals), sinks=sinks, member_sinks=sinks,
        heartbeat_timeout=vals["mesh.heartbeat"],
        journal=vals["mesh.journal"] or None)
    serve_srv, serve_pub = _start_serve_mesh(vals, mesh.coordinator)
    query = None
    if vals["query.addr"]:
        qhost, qport = _host_port(vals["query.addr"], 8082)
        query = QueryServer(None, qport, qhost,
                            mesh=mesh.coordinator).start()
    elapsed = mesh.run()
    merged = sum(len(v) for v in mesh.coordinator.merged.values())
    log.info("mesh aggregated %d flows with %d workers in %.2fs "
             "(%.0f flows/sec, %d merged windows)", produced, n_workers,
             elapsed, produced / max(elapsed, 1e-9), merged)
    if query:
        query.stop()
    if serve_pub:
        serve_pub.stop()
    if serve_srv:
        serve_srv.stop()
    if server:
        server.stop()
    return 0


def pipeline_main(argv=None) -> int:
    """In-process end-to-end demo (the compose *-mock topology equivalent)."""
    fs = _processor_flags(_gen_flags(_common_flags(FlagSet("pipeline"))))
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    from .obs.trace import TRACER
    from .utils.faults import FAULTS

    TRACER.configure(vals["obs.trace"])
    FAULTS.configure(vals["faults"])
    _apply_backend(vals["processor.backend"])
    if vals["mesh.workers"]:
        return _pipeline_mesh(vals)
    from .engine import StreamWorker, WorkerConfig
    from .schema import wire
    from .transport import Consumer, InProcessBus

    bus = InProcessBus()
    bus.create_topic(vals["kafka.topic"], vals["bus.partitions"])
    gen = _make_generator(vals)
    t0 = time.perf_counter()
    produced = 0
    while produced < vals["produce.count"]:
        n = min(8192, vals["produce.count"] - produced)
        bus.produce_many(vals["kafka.topic"], _batch_frames(gen.batch(n)))
        produced += n
    log.info("produced %d flows in %.2fs", produced, time.perf_counter() - t0)

    consumer = Consumer(bus, vals["kafka.topic"], fixedlen=True)
    server = _start_metrics(vals["metrics.addr"], 8081)
    worker = StreamWorker(
        consumer,
        _build_models(vals),
        _vals_sinks(vals),
        _worker_config(vals),
    )
    serve_srv, serve_store = _start_serve_worker(vals, worker)
    query = None
    if vals["query.addr"]:
        from .engine.query_api import QueryServer

        qhost, qport = _host_port(vals["query.addr"], 8082)
        query = QueryServer(worker, qport, qhost,
                            serve=serve_store).start()
    t0 = time.perf_counter()
    worker.run(stop_when_idle=True)
    dt = time.perf_counter() - t0
    log.info("aggregated %d flows in %.2fs (%.0f flows/sec)",
             worker.flows_seen, dt, worker.flows_seen / max(dt, 1e-9))
    if query:
        query.stop()
    if serve_srv:
        serve_srv.stop()
    if server:
        server.stop()
    return 0


def _fmt_lineage(rec: dict) -> str:
    """One human line per window + one per contribution — the after-
    the-fact answer to "which shard stalled / built / missed this
    window"."""
    carries = ",".join(rec.get("carries_promoted") or []) or "-"
    members = ",".join(rec.get("members") or
                       sorted({c["member"] for c in rec["contributions"]
                               if c.get("member")})) or "-"
    head = (f"{rec['model']} @ {rec['slot']} [{rec['status']}] "
            f"members={members} contribs={len(rec['contributions'])} "
            f"carries={carries} late={rec.get('late', 0)}")
    if rec["status"] == "merged":
        head += (f" rows={rec.get('rows')} "
                 f"barrier_wait={rec.get('barrier_wait_s')}s "
                 f"merge={rec.get('merge_wall_s')}s")
    lines = [head]
    for c in rec["contributions"]:
        ranges = c.get("ranges")
        rng = " ".join(f"{p}:[{r[0]},{r[1]})"
                       for p, r in sorted((ranges or {}).items(),
                                          key=lambda kv: int(kv[0])))
        lag = ""
        if c.get("accepted") is not None and c.get("submitted") is not None:
            lag = f" xfer={c['accepted'] - c['submitted']:+.3f}s"
        lines.append(f"    {c.get('member') or '?'} sub={c.get('sub')} "
                     f"{c['kind']} chunk={c.get('chunk')} "
                     f"{rng or 'ranges=-'}{lag}")
    return "\n".join(lines)


def lineage_main(argv=None) -> int:
    """meshscope lineage query: ask a mesh coordinator's /debug/lineage
    ledger which members built each merged window, from which offset
    ranges, through which path (closed submission / promoted carry /
    late partial), and how long the barrier and merge took."""
    import json as _json
    import urllib.parse
    import urllib.request

    fs = FlagSet("lineage")
    fs.string("loglevel", "info", "Log level")
    fs.string("mesh.coordinator", "http://127.0.0.1:8090",
              "Mesh coordinator base URL to query")
    fs.string("lineage.model", "", "Restrict to one model (empty = all)")
    fs.integer("lineage.slot", -1, "Restrict to one window slot "
                                   "(epoch seconds; -1 = all)")
    fs.boolean("lineage.raw", False, "Print raw JSON records instead "
                                     "of the summary lines")
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    params = {}
    if vals["lineage.model"]:
        params["model"] = vals["lineage.model"]
    if vals["lineage.slot"] >= 0:
        params["slot"] = str(vals["lineage.slot"])
    url = vals["mesh.coordinator"].rstrip("/") + "/debug/lineage"
    if params:
        url += "?" + urllib.parse.urlencode(params)
    with urllib.request.urlopen(url, timeout=10) as resp:
        records = _json.loads(resp.read().decode())
    if vals["lineage.raw"]:
        print(_json.dumps(records, indent=2, default=str))
        return 0
    if not records:
        print("no lineage records (nothing merged or pending "
              "in the retention window)")
        return 0
    for rec in records:
        print(_fmt_lineage(rec))
    return 0


def replay_main(argv=None) -> int:
    """flowchaos dead-letter replay: re-ingest batches that exhausted
    their sink retry budget (``<dir>/deadletter/*.dlq.json``, written by
    ``ResilientSink``) into any sink spec. Files are deleted only after
    every sink accepted them (at-least-once — merging tables absorb a
    replay-of-the-replay exactly like worker replays); the first
    failing file aborts so spill order is preserved for the next run."""
    from .sink.resilient import deadletter_files, replay_deadletter

    fs = FlagSet("replay")
    fs.string("loglevel", "info", "Log level")
    fs.string("replay.dir", "", "Sink dead-letter root (the directory "
                                "passed as -sink.deadletter; its "
                                "deadletter/ subdir holds the spill)")
    fs.boolean("replay.delete", True, "Delete each file after every "
                                      "sink accepted it (false = keep, "
                                      "for dry runs)")
    fs.string("sink", "stdout", "stdout | sqlite:PATH | postgres:DSN | "
                                "clickhouse:URL (comma separated)")
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    if not vals["replay.dir"]:
        log.error("replay needs -replay.dir (the -sink.deadletter root)")
        return 2
    pending = deadletter_files(vals["replay.dir"])
    if not pending:
        log.info("no dead-letter files under %s; nothing to replay",
                 vals["replay.dir"])
        return 0
    sinks = _make_sinks(vals["sink"])
    files, rows = replay_deadletter(vals["replay.dir"], sinks,
                                    delete=vals["replay.delete"])
    log.info("replayed %d file(s) / %d row(s) into %s", files, rows,
             vals["sink"])
    return 0


def gateway_main(argv=None) -> int:
    """flowgate replica: mirror upstream snapshot streams (worker or
    mesh-coordinator flowserve surfaces, ``/sub/snapshot``) into a
    local store and serve ``/query/*`` from this process's own cores.
    Run K of these behind client-side consistent hashing
    (gateway/ring.py) for a horizontally scaled read tier — see
    docs/ARCHITECTURE.md "flowgate"."""
    fs = FlagSet("gateway")
    fs.string("loglevel", "info", "Log level")
    fs.string("gateway.upstream", "",
              "Comma-separated upstream flowserve host:port list to "
              "subscribe to (first = the primary stream this replica "
              "serves)")
    fs.string("gateway.listen", "127.0.0.1:8084",
              "host:port the gateway serves /query/* on")
    fs.number("gateway.poll", 0.25,
              "Subscription poll cadence in seconds (deltas ship "
              "between versions; a gap forces a full resync)")
    fs.string("metrics.addr", "", "host:port for /metrics (empty "
                                  "disables)")
    fs.string("faults", "", "flowchaos deterministic fault plan "
                            "(gateway.poll is the flowgate seam)",
              env="FLOWTPU_FAULTS")
    fs.boolean("gateway.adopt-restart", False,
               "Adopt an upstream RESTART automatically: when the "
               "subscribed stream comes back with a lower version and "
               "kind=full, swap to it (availability) instead of "
               "holding the pre-restart snapshot until the upstream "
               "version catches up (monotone reads, the default)")
    fs.integer("guard.serve_queue", 0,
               "flowguard read-side admission: max concurrently "
               "computing queries on this replica; past it + the "
               "deadline, 503 with Retry-After (0 = unbounded)")
    fs.number("guard.serve_deadline", 0.1,
              "flowguard admission deadline seconds a query may wait "
              "for a compute slot before it is shed with 503")
    fs.string("history.dir", "",
              "flowhistory archive directory: persist the mirrored "
              "delta chain and answer /query/range past upstream "
              "retention plus ?at=/?version= time travel from this "
              "replica (empty disables)")
    fs.integer("history.keyframe", 64,
               "flowhistory keyframe cadence: full snapshot every N "
               "deltas (smaller = faster reconstruction, bigger "
               "archive)")
    fs.integer("history.retain", 1 << 30,
               "flowhistory archive byte bound; whole oldest keyframe "
               "segments are evicted past it")
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    if not vals["gateway.upstream"]:
        log.error("gateway needs -gateway.upstream host:port[,host:port]")
        return 2
    from .gateway import SnapshotGateway
    from .serve import ServeServer
    from .utils.faults import FAULTS

    FAULTS.configure(vals["faults"])
    server = _start_metrics(vals["metrics.addr"], 8081)
    archive = None
    if vals["history.dir"]:
        from .history import ArchiveWriter

        archive = ArchiveWriter(vals["history.dir"],
                                keyframe_every=vals["history.keyframe"],
                                retain_bytes=vals["history.retain"])
    gw = SnapshotGateway(
        [u.strip() for u in vals["gateway.upstream"].split(",")
         if u.strip()],
        poll=vals["gateway.poll"],
        adopt_restart=vals["gateway.adopt-restart"],
        archive=archive)
    host, port = _host_port(vals["gateway.listen"], 8084)
    if archive is not None:
        from .history import ArchiveReader, HistoryServer

        serve = HistoryServer(
            ArchiveReader(vals["history.dir"]), store=gw.store,
            port=port, host=host,
            max_inflight=vals["guard.serve_queue"],
            deadline=vals["guard.serve_deadline"]).start()
    else:
        serve = ServeServer(
            gw.store, port, host,
            max_inflight=vals["guard.serve_queue"],
            deadline=vals["guard.serve_deadline"]).start()
    gw.serve_on(serve).start()
    log.info("flowgate replica serving %s on http://%s:%d/query",
             vals["gateway.upstream"], host, serve.port)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        gw.stop()
        serve.stop()
        if archive is not None:
            archive.close()
        if server:
            server.stop()
    return 0


def history_main(argv=None) -> int:
    """flowhistory tier: subscribe to a flowserve surface (worker, mesh
    coordinator, or gateway replica), archive the delta chain to disk
    as keyframe segments, and serve time-travel queries —
    ``/query/topk?at=``, ``/query/estimate?version=``, and
    ``/query/range`` reaching past upstream retention — plus the live
    head, mirrored like a gateway replica. See docs/ARCHITECTURE.md
    "flowhistory"."""
    fs = FlagSet("history")
    fs.string("loglevel", "info", "Log level")
    fs.string("history.upstream", "",
              "Upstream flowserve host:port whose snapshot stream is "
              "archived (a worker's/coordinator's -serve.addr or a "
              "gateway's -gateway.listen)")
    fs.string("history.listen", "127.0.0.1:8085",
              "host:port the flowhistory tier serves /query/* and "
              "/history/index on")
    fs.string("history.dir", "./flowhistory",
              "Archive directory for keyframe segments")
    fs.integer("history.keyframe", 64,
               "Keyframe cadence: full snapshot every N deltas "
               "(smaller = faster reconstruction, bigger archive)")
    fs.integer("history.retain", 1 << 30,
               "Archive byte bound; whole oldest keyframe segments "
               "are evicted past it")
    fs.number("history.poll", 0.25,
              "Subscription poll cadence in seconds")
    fs.string("metrics.addr", "", "host:port for /metrics (empty "
                                  "disables)")
    fs.string("faults", "", "flowchaos deterministic fault plan",
              env="FLOWTPU_FAULTS")
    fs.integer("guard.serve_queue", 0,
               "flowguard read-side admission: max concurrently "
               "computing queries; past it + the deadline, 503 with "
               "Retry-After (0 = unbounded)")
    fs.number("guard.serve_deadline", 0.1,
              "flowguard admission deadline seconds a query may wait "
              "for a compute slot before it is shed with 503")
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    if not vals["history.upstream"]:
        log.error("history needs -history.upstream host:port")
        return 2
    from .history import ArchiveReader, ArchiveWriter, HistoryServer
    from .utils.faults import FAULTS

    FAULTS.configure(vals["faults"])
    server = _start_metrics(vals["metrics.addr"], 8081)
    host, port = _host_port(vals["history.listen"], 8085)
    serve = HistoryServer(
        ArchiveReader(vals["history.dir"]),
        port=port, host=host,
        max_inflight=vals["guard.serve_queue"],
        deadline=vals["guard.serve_deadline"]).start()
    writer = ArchiveWriter(vals["history.dir"],
                           keyframe_every=vals["history.keyframe"],
                           retain_bytes=vals["history.retain"],
                           upstream=vals["history.upstream"],
                           poll=vals["history.poll"],
                           store=serve.store).start()
    log.info("flowhistory archiving %s into %s, serving on "
             "http://%s:%d/query", vals["history.upstream"],
             vals["history.dir"], host, serve.port)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        writer.stop()
        serve.stop()
        if server:
            server.stop()
    return 0


def collector_main(argv=None) -> int:
    """UDP flow collector (in-framework GoFlow replacement): listens for
    sFlow on 6343 and NetFlow/IPFIX on 2055, produces FlowMessages."""
    fs = _common_flags(FlagSet("collector"))
    fs.string("listen.netflow", "0.0.0.0:2055", "NetFlow/IPFIX UDP addr "
                                                "(empty disables)")
    fs.string("listen.sflow", "0.0.0.0:6343", "sFlow UDP addr (empty disables)")
    fs.string("metrics.addr", "127.0.0.1:8080", "host:port for /metrics")
    fs.string("out", "", "Append frames to this file instead of Kafka")
    fs.number("run.seconds", 0.0, "Exit after this long (0 = run forever)")
    vals = fs.parse(argv if argv is not None else sys.argv[2:])
    set_level(vals["loglevel"])
    from .collector import CollectorConfig, CollectorServer

    def parse_addr(s):
        if not s:
            return None
        return _host_port(s, 0, default_host="0.0.0.0")  # UDP listen addr

    if vals["out"]:
        from .schema import wire

        out_f = open(vals["out"], "ab")

        class FileProducer:
            def send(self, msg):
                out_f.write(wire.encode_frame(msg))

        producer = FileProducer()
    else:
        from .transport import kafka as tkafka

        if not tkafka.available():
            log.error("no Kafka client; use -out FILE")
            return 2
        producer = tkafka.KafkaProducerAdapter(
            vals["kafka.brokers"], vals["kafka.topic"], vals["proto.fixedlen"]
        )
    server = _start_metrics(vals["metrics.addr"], 8080)
    collector = CollectorServer(
        producer,
        CollectorConfig(
            netflow_addr=parse_addr(vals["listen.netflow"]),
            sflow_addr=parse_addr(vals["listen.sflow"]),
        ),
    ).start()
    try:
        if vals["run.seconds"]:
            time.sleep(vals["run.seconds"])
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        collector.stop()
        if hasattr(producer, "flush"):
            producer.flush()  # drain the async Kafka batch queue
        if server:
            server.stop()
        if vals["out"]:
            out_f.close()
    return 0


_COMMANDS = {
    "mocker": mocker_main,
    "processor": processor_main,
    "inserter": inserter_main,
    "pipeline": pipeline_main,
    "collector": collector_main,
    "lineage": lineage_main,
    "replay": replay_main,
    "gateway": gateway_main,
    "history": history_main,
}


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if not argv or argv[0] in ("-h", "-help", "--help"):
        print("usage: flow_pipeline_tpu.cli <mocker|processor|inserter|"
              "pipeline|collector|lineage|replay|gateway|history> "
              "[-flags]\n"
              "Run '<cmd> -help' for flags.")
        return 0 if argv else 2
    cmd = _COMMANDS.get(argv[0])
    if cmd is None:
        print(f"unknown command {argv[0]!r}", file=sys.stderr)
        return 2
    try:
        return cmd(argv[1:]) or 0
    except (ValueError, OSError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2


def mocker_entry() -> None:  # console-script shims
    sys.exit(main(["mocker"] + sys.argv[1:]))


def processor_entry() -> None:
    sys.exit(main(["processor"] + sys.argv[1:]))


def inserter_entry() -> None:
    sys.exit(main(["inserter"] + sys.argv[1:]))


def pipeline_entry() -> None:
    sys.exit(main(["pipeline"] + sys.argv[1:]))


def collector_entry() -> None:
    sys.exit(main(["collector"] + sys.argv[1:]))


def lineage_entry() -> None:
    sys.exit(main(["lineage"] + sys.argv[1:]))


def replay_entry() -> None:
    sys.exit(main(["replay"] + sys.argv[1:]))


def gateway_entry() -> None:
    sys.exit(main(["gateway"] + sys.argv[1:]))


def history_entry() -> None:
    sys.exit(main(["history"] + sys.argv[1:]))


if __name__ == "__main__":
    sys.exit(main())
