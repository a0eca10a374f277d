"""What runs (ISSUE 46): one table of (model set, WorkerConfig, backend,
consumer) -> (pipeline, executor, ``hh_sketch`` label, the models'
lateness afterwards, the words logged or the exception), held three
ways: against what a ``StreamWorker`` built from the row shows (so the
rows hold on the tree before ``engine/dataplane.py`` too), against
``dataplane.choose`` alone (pure: it builds and logs nothing), and, for
the rows the CLI can express, against ``cli._build_models``'s own
resolution of ``-hh.sketch auto``.

The model sets are ``cli._build_models``'s at a small size, the configs
``cli._worker_config``'s with the fields no flag reaches laid over
them. The words are written out here, not imported: a start-up line is
something operators grep for.
"""

import dataclasses
import logging

import jax
import pytest

from flow_pipeline_tpu import cli
from flow_pipeline_tpu.engine.worker import StreamWorker
from flow_pipeline_tpu.obs import REGISTRY
from flow_pipeline_tpu.obs.buildinfo import BUILD_INFO
from flow_pipeline_tpu.utils.flags import FlagSet

SMALL = ["-processor.batch", "256", "-sketch.width", "2048",
         "-sketch.capacity", "512"]
EXACT_ONLY = ["-model.talkers=false", "-model.ips=false"]
INVERTIBLE = ["-hh.sketch", "invertible", "-model.ports=false",
              "-model.ddos=false"]
BACKBONE = ["-model.pairs=true", "-model.ports=false", "-model.ddos=false"]
HOST, OFF, ON = (["-sketch.backend", "host"],
                 ["-processor.hostassist", "off"],
                 ["-processor.hostassist", "on"])
LATE = ["-window.lateness", "7"]
MESH = ["-processor.mesh", "4"]
NO_AUDIT = ["-obs.audit", "off"]
NO_PREFETCH = ["-feed.prefetch", "0"]
STAGED = ["-ingest.fused", "off"]  # needs no native library
SPREAD = ["-spread.enabled=true", "-spread.width", "256",
          "-spread.regs", "16"]

HOST_NEEDS_GROUPING = (
    "sketch.backend=host needs the host-grouped pipeline (CPU backend or "
    "-processor.hostassist on); keeping the device sketch step")
NOT_FUSABLE = "model set not fusable; using per-model updates"
INVERTIBLE_FALLBACK = (
    "hh.sketch=invertible needs the host sketch pipeline "
    "(-sketch.backend=host + CPU backend or -processor.hostassist on); "
    "falling back to the per-model numpy path for this worker")
NO_PREFETCH_WRAP = ("ingest pipelined mode needs the prefetch wrap "
                    "(feed.prefetch > 0); using the serial path")
FUSED_NOT_SELECTED = (
    "ingest_fused='on' but the host sketch pipeline was not selected — it "
    "needs a fusable model set and host-grouped pre-aggregation (CPU "
    "backend or -processor.hostassist on)")


def spread_planes(home):
    return ("spread detectors superspreaders, portscan: register planes "
            + {"device": "on the device, updated inside the fused step",
               "host": "in host memory, folded between device steps"}[home])


def audit_nothing(mode="sample"):
    return (f"obs.audit={mode}: no sketch-backed families in the model "
            f"set; nothing to audit")


def audit_off(mode="sample"):
    return (f"obs.audit={mode} needs the host-grouped pipeline (CPU "
            f"backend or -processor.hostassist on); sketch accuracy "
            f"audit is off for this worker")


def late(pipeline, *names, lateness=7):
    return [f"-window.lateness {lateness}: on the {pipeline} dataplane "
            f"{name} still drops the rows that arrive after their unit "
            f"rolled, and counts them in late_flows_dropped"
            for name in names]


HELD = ("top_talkers", "top_src_ips", "top_dst_ips", "top_src_ports",
        "top_dst_ports", "ddos_alerts")


@dataclasses.dataclass(frozen=True)
class Row:
    id: str
    argv: tuple = ()            # the CLI's flags: models and config
    over: tuple = ()            # WorkerConfig fields no flag reaches
    backend: str = "cpu"        # what jax.default_backend() answers
    consumer: bool = True       # False: StreamWorker(None, ...)
    unknown: bool = False       # a model no pipeline knows joins the set
    no_models: bool = False
    # -> the answer
    pipeline: str | None = None
    executor: bool = False
    hh_sketch: str = "table"
    audit: str = "off"
    lateness: int = 0           # every holding model's, afterwards
    spread: str = "none"        # where the spread detectors' planes live
    words: tuple = ()
    exc: tuple | None = None    # (type, a part of its message)

    def __str__(self):
        return self.id


def row(id, *argv, **kw):
    kw["words"] = tuple(kw.get("words", ()))
    kw["over"] = tuple(kw.pop("over", {}).items())
    return Row(id, tuple(x for part in argv for x in part), **kw)


# The first seven are what the benchmark's cells run (BENCHMARK.json):
# their processor flags at a small size, under the backend they meet.
ROWS = [
    row("cell:estate-catchup,estate-live,estate-as64k-catchup",
        backend="tpu", pipeline="FusedPipeline", words=[audit_off()]),
    row("cell:estate-2part-catchup", LATE, backend="tpu",
        pipeline="FusedPipeline", lateness=7, words=[audit_off()]),
    row("cell:estate-sliding-catchup", ["-window.slide", "30"],
        backend="tpu", pipeline="FusedPipeline", words=[audit_off()]),
    row("cell:hh-backbone-catchup", BACKBONE, backend="tpu",
        pipeline="FusedPipeline", words=[audit_off()]),
    row("cell:estate-mesh4-catchup", MESH, backend="tpu",
        pipeline="ShardedPipeline", words=[audit_off()]),
    row("cell:estate-spread-catchup", SPREAD, backend="tpu",
        pipeline="FusedPipeline", spread="device",
        words=[spread_planes("device"), audit_off()]),
    # tier-1's stand-in for the one-chip cells (benchmark/tests, and
    # every test that wants the chip's dataplane on the CPU)
    row("cells-on-cpu:hostassist-off", OFF, pipeline="FusedPipeline",
        words=[audit_off()]),
    row("cells-on-cpu:mesh4-lateness", MESH, LATE,
        pipeline="ShardedPipeline", lateness=7, words=[audit_off()]),
    # the CPU's default: host grouping in front of the device step
    row("cpu-default", pipeline="HostGroupPipeline", executor=True,
        audit="sample"),
    row("cpu-default:raw-consumer", NO_PREFETCH,
        pipeline="HostGroupPipeline", audit="sample",
        words=[NO_PREFETCH_WRAP]),
    row("cpu-default:no-consumer", consumer=False,
        pipeline="HostGroupPipeline", audit="sample"),
    row("cpu-default:serial", over={"ingest_mode": "serial"},
        pipeline="HostGroupPipeline", audit="sample"),
    row("cpu-default:one-shard-numpy-grouping",
        over={"ingest_shards": 1, "ingest_native_group": False},
        pipeline="HostGroupPipeline", executor=True, audit="sample"),
    row("cpu-default:audit-full", ["-obs.audit", "full"],
        pipeline="HostGroupPipeline", executor=True, audit="full"),
    row("cpu-default:audit-off", NO_AUDIT, pipeline="HostGroupPipeline",
        executor=True),
    row("cpu-default:lateness", LATE, pipeline="HostGroupPipeline",
        executor=True, audit="sample",
        words=late("HostGroupPipeline", *HELD)),
    # the spread detectors' planes go where the pipeline keeps them
    row("cpu-default:spread", SPREAD, pipeline="HostGroupPipeline",
        executor=True, audit="sample", spread="host",
        words=[spread_planes("host")]),
    row("spread:hostassist-off", SPREAD, OFF, LATE,
        pipeline="FusedPipeline", lateness=7, spread="device",
        words=[spread_planes("device"), audit_off()]),
    row("spread:host-sketch", SPREAD, HOST, STAGED,
        pipeline="HostSketchPipeline", executor=True, hh_sketch="mixed",
        audit="sample", spread="host", words=[spread_planes("host")]),
    row("spread:unfused", SPREAD, ["-processor.fused=false"],
        spread="host", words=[spread_planes("host"), audit_off()]),
    row("hostassist-on:tpu", ON, backend="tpu",
        pipeline="HostGroupPipeline", executor=True, audit="sample"),
    row("exact-only:cpu", EXACT_ONLY, pipeline="HostGroupPipeline",
        executor=True, hh_sketch="none", audit="sample",
        words=[audit_nothing()]),
    row("exact-only:hostassist-off", EXACT_ONLY, OFF, ["-obs.audit", "full"],
        pipeline="FusedPipeline", hh_sketch="none",
        words=[audit_nothing("full")]),
    row("audit-off:hostassist-off", OFF, NO_AUDIT,
        pipeline="FusedPipeline"),
    # -sketch.backend host
    row("host-sketch:cpu", HOST, STAGED, pipeline="HostSketchPipeline",
        executor=True, hh_sketch="mixed", audit="sample"),
    row("host-sketch:table", HOST, STAGED, ["-hh.sketch", "table"],
        pipeline="HostSketchPipeline", executor=True, audit="sample"),
    row("host-sketch:lateness", LATE, STAGED,
        over={"sketch_backend": "host"}, pipeline="HostSketchPipeline",
        executor=True, audit="sample",
        words=late("HostSketchPipeline", *HELD)),
    row("host-sketch:hostassist-off", HOST, OFF, pipeline="FusedPipeline",
        words=[HOST_NEEDS_GROUPING, audit_off()]),
    row("host-sketch:tpu", HOST, backend="tpu", pipeline="FusedPipeline",
        words=[HOST_NEEDS_GROUPING, audit_off()]),
    row("host-sketch:mesh4", HOST, MESH, pipeline="ShardedPipeline",
        words=[audit_off()]),
    row("ingest-fused-on:hostassist-off", HOST, OFF, ["-ingest.fused", "on"],
        words=[HOST_NEEDS_GROUPING],
        exc=(RuntimeError, FUSED_NOT_SELECTED)),
    row("ingest-fused-on:unfused", HOST, ["-ingest.fused", "on"],
        ["-processor.fused=false"], exc=(RuntimeError, FUSED_NOT_SELECTED)),
    row("ingest-fused-on:mesh4", HOST, MESH, ["-ingest.fused", "on"],
        exc=(RuntimeError, FUSED_NOT_SELECTED)),
    row("ingest-fused-on:device", ["-ingest.fused", "on"],
        exc=(ValueError, "ingest_fused='on' requires sketch_backend='host'")),
    # invertible families: the host sketch pipeline or the numpy path
    row("invertible:host-sketch", INVERTIBLE, HOST, ON, STAGED,
        pipeline="HostSketchPipeline", executor=True,
        hh_sketch="invertible", audit="sample"),
    row("invertible:cpu-device", INVERTIBLE, hh_sketch="invertible",
        words=[INVERTIBLE_FALLBACK, audit_off()]),
    row("invertible:hostassist-off", INVERTIBLE, HOST, OFF,
        hh_sketch="invertible",
        words=[HOST_NEEDS_GROUPING, INVERTIBLE_FALLBACK, audit_off()]),
    row("invertible:unfused", INVERTIBLE, ["-processor.fused=false"],
        hh_sketch="invertible", words=[audit_off()]),
    # no pipeline at all
    row("unfused", ["-processor.fused=false"], words=[audit_off()]),
    row("unfused:lateness", ["-processor.fused=false"], LATE, lateness=7,
        words=[audit_off()]),
    row("unfused:bad-hostassist", ["-processor.fused=false"],
        ["-processor.hostassist", "maybe"], NO_AUDIT),
    row("mesh4:unknown-model", MESH, unknown=True,
        words=[NOT_FUSABLE, audit_off()]),
    row("unknown-model:exact-only", EXACT_ONLY, NO_PREFETCH, unknown=True,
        hh_sketch="none", words=[NOT_FUSABLE, audit_nothing()]),
    row("no-models", no_models=True, hh_sketch="none"),
    # what the config may not say
    row("bad:ingest_mode", over={"ingest_mode": "threaded"},
        exc=(ValueError, "ingest_mode must be pipelined|serial, "
                         "got 'threaded'")),
    row("bad:sketch_backend", ["-sketch.backend", "gpu"],
        exc=(ValueError, "sketch_backend must be device|host, got 'gpu'")),
    row("bad:ingest_fused", ["-ingest.fused", "maybe"],
        exc=(ValueError, "ingest_fused must be auto|on|off, got 'maybe'")),
    row("bad:ingest_threads", ["-ingest.threads", "-1"],
        exc=(ValueError, "ingest_threads must be >= 0 (0 = auto), got -1")),
    row("bad:obs_audit", ["-obs.audit", "all"],
        exc=(ValueError, "obs_audit must be off|sample|full, got 'all'")),
    row("bad:guard_lag", ["-guard.lag", "-1"],
        exc=(ValueError, "guard_lag must be >= 0 (0 = disarmed), "
                         "got -1.0")),
    row("bad:host_assist", ["-processor.hostassist", "maybe"],
        exc=(ValueError, "host_assist must be auto|on|off, got 'maybe'")),
]
# the rows the CLI can express whole, with -hh.sketch left at auto
AUTO = [r for r in ROWS if "-hh.sketch" not in r.argv
        and not (r.over or r.unknown or r.no_models)]


class _Other:
    def update(self, batch):
        pass


class _Idle:
    """A consumer that is never polled: start-up is what is tested."""

    def poll(self, max_messages):
        return None


def _parse(r: Row):
    fs = cli._processor_flags(cli._common_flags(FlagSet("processor")))
    return fs.parse([*SMALL, *r.argv])


def _build(r: Row, monkeypatch, role="worker"):
    """(models, config) as ``processor_main`` would build them."""
    monkeypatch.setattr(jax, "default_backend", lambda: r.backend)
    vals = _parse(r)
    models = {} if r.no_models else cli._build_models(vals)
    if r.unknown:
        models["other"] = _Other()
    config = dataclasses.replace(cli._worker_config(vals), build_role=role,
                                 **dict(r.over))
    return models, config


def _lateness(models) -> set:
    return {m.lateness for m in models.values() if hasattr(m, "lateness")}


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.INFO)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


@pytest.fixture
def said():
    # the flowtpu root logger does not propagate; attach directly
    logger, handler = logging.getLogger("flowtpu.worker"), _Capture()
    logger.addHandler(handler)
    yield handler.lines
    logger.removeHandler(handler)


def _build_info(role: str) -> dict:
    """The labels of flow_build_info's one series for ``role``, which is
    taken out again: other tests render the gauge."""
    g = REGISTRY.gauge(*BUILD_INFO)
    (labels,) = [dict(k) for k in list(g._values) if dict(k)["role"] == role]
    g.remove(**labels)
    return labels


@pytest.mark.parametrize("r", ROWS, ids=str)
def test_a_worker_runs_what_the_row_says(r, monkeypatch, said):
    models, config = _build(r, monkeypatch, role=f"row:{r.id}")
    consumer = _Idle() if r.consumer else None
    if r.exc:
        with pytest.raises(r.exc[0]) as err:
            StreamWorker(consumer, models, [], config)
        assert r.exc[1] in str(err.value)
        assert tuple(said) == r.words
        return
    worker = StreamWorker(consumer, models, [], config)
    fused = worker.fused
    assert (type(fused).__name__ if fused is not None else None) == r.pipeline
    assert (worker.executor is not None) == r.executor
    assert (worker.flusher is not None) == r.executor
    assert tuple(said) == r.words
    assert _lateness(models) <= {r.lateness}
    assert {m.model.on_device for m in models.values()
            if getattr(getattr(m, "model", None), "snapshot_kind", None)
            == "windowed_spread"} <= {r.spread == "device"}
    assert _build_info(config.build_role)["hh_sketch"] == r.hh_sketch
    audit = getattr(fused, "audit", None)  # None without an hh family
    assert (audit.mode if audit is not None else "off") == (
        "off" if r.hh_sketch == "none" else r.audit)
    lazy = {getattr(m, "lazy_extract", False) for m in models.values()
            if hasattr(getattr(m, "model", None), "top_lazy")}
    assert lazy <= {r.executor}


@pytest.mark.parametrize("r", ROWS, ids=str)
def test_choose_answers_the_row_and_touches_nothing(r, monkeypatch, said):
    from flow_pipeline_tpu.engine.dataplane import choose

    models, config = _build(r, monkeypatch)
    before = _lateness(models)
    prefetched = bool(config.prefetch) if r.consumer else None
    if r.exc and r.exc[0] is ValueError:  # a config it may not say
        with pytest.raises(ValueError) as err:
            choose(models, config, prefetched)
        assert r.exc[1] in str(err.value)
        return
    choice = choose(models, config, prefetched)
    assert [fmt % args for _level, fmt, args in choice.words] == list(r.words)
    assert not said and _lateness(models) == before     # pure
    if r.exc:
        assert type(choice.error) is r.exc[0]
        assert r.exc[1] in str(choice.error)
        return
    assert choice.error is None
    name = choice.pipeline.__name__ if choice.pipeline else None
    assert name == r.pipeline
    assert choice.pipelined == r.executor
    assert choice.hh_sketch == r.hh_sketch
    assert choice.audit == r.audit
    assert choice.kwargs.get("audit", "off") == r.audit
    held = {n for n, m in models.items() if getattr(m, "lateness", 0)}
    assert set(choice.lateness_dropped) == (held if r.lateness == 0
                                            else set())


@pytest.mark.parametrize("r", AUTO, ids=str)
def test_the_cli_resolves_auto_to_what_the_choice_serves(r, monkeypatch):
    """``-hh.sketch auto`` flips a cascade family to the invertible
    sketch only where the worker's choice folds it: never onto the
    per-model numpy path."""
    from flow_pipeline_tpu.engine.dataplane import choose

    models, config = _build(r, monkeypatch)
    resolved = {n: m.model.config.hh_sketch for n, m in models.items()
                if getattr(getattr(m, "model", None), "snapshot_kind",
                           None) == "windowed_hh"}
    try:
        choice = choose(models, config, r.consumer or None)
    except ValueError:
        assert set(resolved.values()) <= {"table"}
        return
    assert INVERTIBLE_FALLBACK not in [w[1] for w in choice.words]
    serves = choice.pipeline is not None and choice.pipeline.serves_invertible
    if serves and "top_talkers" in resolved and len(resolved) > 1:
        # the 5-tuple root keeps the table; its strict subsets flip
        assert resolved.pop("top_talkers") == "table"
        assert set(resolved.values()) == {"invertible"}
    else:
        assert set(resolved.values()) <= {"table"}
