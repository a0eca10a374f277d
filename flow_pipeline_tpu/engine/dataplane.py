"""What runs: the one place a worker's dataplane is chosen.

``choose(models, config, prefetched)`` picks among ``FusedPipeline``
(one jitted step a batch: every one-chip cell), ``ShardedPipeline``
(the mesh-sharded kinds: the four-chip cell), ``HostGroupPipeline``
(host-grouped pre-aggregation in front of the device step: the default
on a CPU), ``HostSketchPipeline`` (``sketch_backend="host"`` on top of
it) and the per-model loop, from what it can observe: the models' types
(each pipeline's ``supported``), their ``hh_sketch`` and ``lateness``,
the ``WorkerConfig`` fields, the default backend (through
``HostGroupPipeline.eligible``) and whether the consumer is
prefetch-wrapped. The pipeline chosen also says where the spread
detectors' planes live: on the device under one whose step updates them
(``spread_in_step``), host numpy under every other; the words say
which. It builds nothing and logs nothing: the ``Choice`` it
returns names the class and its arguments, what follows from the class
(executor + flusher, the audit's mode, which models run at lateness 0)
and the words to log, and ``StreamWorker.__init__`` carries it out.

What a pipeline can do is a class attribute (``honours_lateness``,
``has_prepare_split``, ``serves_invertible``, ``feeds_audit``,
``spread_in_step``; the
``False`` defaults are ``WindowLifecycle``'s), never a class name
tested here or in the worker.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from logging import INFO, WARNING
from typing import Any, Optional

from .fused import FusedPipeline
from .hostfused import HostGroupPipeline
from .windowed import WindowedHeavyHitter


@dataclass(frozen=True)
class Choice:
    # the class to build as ``pipeline(models, **kwargs)``; None: the
    # per-model loop
    pipeline: Optional[type] = None
    kwargs: dict = field(default_factory=dict)
    # flow_build_info's ``hh_sketch`` label: the sketch family the
    # models run, whichever path serves them
    hh_sketch: str = "none"
    # PipelinedExecutor + AsyncFlusher engage (the wrappers then
    # extract lazily: the flusher resolves a closed window's top)
    pipelined: bool = False
    # the audit mode the built pipeline is given: ``config.obs_audit``
    # where it feeds the audit, "off" everywhere else
    audit: str = "off"
    # models the dataplane runs at lateness 0, each with words below
    lateness_dropped: tuple = ()
    # (level, format, args) in the order to log them
    words: tuple = ()
    # raised once the words are out: a demand the choice cannot meet
    error: Optional[Exception] = None


def _validate(config) -> None:
    """The config's enumerations and ranges, in the order a worker has
    always refused them."""
    if config.ingest_mode not in ("pipelined", "serial"):
        raise ValueError(
            f"ingest_mode must be pipelined|serial, "
            f"got {config.ingest_mode!r}")
    if config.sketch_backend not in ("device", "host"):
        raise ValueError(
            f"sketch_backend must be device|host, "
            f"got {config.sketch_backend!r}")
    if config.ingest_fused not in ("auto", "on", "off"):
        raise ValueError(
            f"ingest_fused must be auto|on|off, "
            f"got {config.ingest_fused!r}")
    if config.ingest_threads < 0:
        raise ValueError(
            f"ingest_threads must be >= 0 (0 = auto), "
            f"got {config.ingest_threads}")
    if config.ingest_fused == "on" and config.sketch_backend != "host":
        raise ValueError(
            "ingest_fused='on' requires sketch_backend='host' — the "
            "fused pass updates the host sketch engine in place")
    if config.obs_audit not in ("off", "sample", "full"):
        raise ValueError(
            f"obs_audit must be off|sample|full, "
            f"got {config.obs_audit!r}")
    if config.guard_lag < 0:
        raise ValueError(
            f"guard_lag must be >= 0 (0 = disarmed), "
            f"got {config.guard_lag}")


def _sketch_backed(models: dict) -> list:
    return [m for m in models.values()
            if isinstance(m, WindowedHeavyHitter)
            and getattr(m.model, "snapshot_kind", None) == "windowed_hh"]


def hh_sketch_mode(models: dict) -> str:
    """The heavy-hitter sketch family a model set runs: "none" without
    a sketch-backed hh family, "mixed" for a table + invertible set
    (-hh.sketch=auto's cascade flip), labeled honestly."""
    modes = {getattr(m.model.config, "hh_sketch", "table")
             for m in _sketch_backed(models)}
    if not modes:
        return "none"
    if modes == {"table"}:
        return "table"
    return "invertible" if modes == {"invertible"} else "mixed"


def host_sketch_serves(fused: bool, sketch_backend: str,
                       host_assist: str) -> bool:
    """Would the host sketch pipeline serve a fusable model set under
    these values? ``cli._build_models`` asks before any model exists
    (-hh.sketch=auto runs a cascade family invertible only where this
    holds, so a default worker never lands on the per-model numpy
    path); ``choose`` asks it for the same branch."""
    return (fused and sketch_backend == "host"
            and HostGroupPipeline.eligible(host_assist))


def choose(models: dict[str, Any], config,
           prefetched: Optional[bool] = None) -> Choice:
    """``prefetched``: the consumer is a PrefetchConsumer (True), a raw
    one (False), or there is none (None)."""
    _validate(config)
    words: list = []
    pipeline, kwargs, host_sketch = None, {}, False
    if config.fused and models:
        if FusedPipeline.supported(models):
            host = dict(shards=config.ingest_shards,
                        native_group=config.ingest_native_group,
                        audit=config.obs_audit)
            host_sketch = host_sketch_serves(
                config.fused, config.sketch_backend, config.host_assist)
            if host_sketch:
                from ..hostsketch import HostSketchPipeline

                pipeline = HostSketchPipeline
                kwargs = dict(host, fused=config.ingest_fused,
                              threads=config.ingest_threads)
            elif config.sketch_backend == "host":
                # the host engine consumes the host-grouped prepare
                # tables; without them there is nothing to feed it
                words.append((
                    WARNING,
                    "sketch.backend=host needs the host-grouped "
                    "pipeline (CPU backend or -processor.hostassist "
                    "on); keeping the device sketch step", ()))
                pipeline = FusedPipeline
            elif HostGroupPipeline.eligible(config.host_assist):
                pipeline, kwargs = HostGroupPipeline, host
            else:
                pipeline = FusedPipeline
        else:
            # a set of the mesh-sharded kinds (-processor.mesh) has
            # programs of its own; the poll is still cut once for all
            from ..parallel.pipeline import ShardedPipeline

            if ShardedPipeline.supported(models):
                pipeline = ShardedPipeline
            else:
                words.append((INFO, "model set not fusable; using "
                                    "per-model updates", ()))
    hh_sketch = hh_sketch_mode(models)
    if (hh_sketch in ("invertible", "mixed") and pipeline is not None
            and not pipeline.serves_invertible):
        # the jitted table step cannot fold invertible state; only the
        # host sketch engine (and the per-model numpy fallback) can:
        # degrade loudly rather than corrupt
        words.append((
            WARNING,
            "hh.sketch=invertible needs the host sketch "
            "pipeline (-sketch.backend=host + CPU backend or "
            "-processor.hostassist on); falling back to the "
            "per-model numpy path for this worker", ()))
        pipeline, kwargs = None, {}
    spread = [name for name, m in models.items()
              if isinstance(m, WindowedHeavyHitter)
              and getattr(m.model, "snapshot_kind", None)
              == "windowed_spread"]
    if spread:
        on_device = pipeline is not None and pipeline.spread_in_step
        words.append((
            INFO, "spread detectors %s: register planes %s",
            (", ".join(spread),
             "on the device, updated inside the fused step" if on_device
             else "in host memory, folded between device steps")))
    dropped = []
    if pipeline is not None and not pipeline.honours_lateness:
        for name, m in models.items():
            if getattr(m, "lateness", 0):
                # no path changes silently: -window.lateness reaches
                # flows_5m alone on this dataplane
                words.append((
                    WARNING,
                    "-window.lateness %d: on the %s dataplane %s still "
                    "drops the rows that arrive after their unit "
                    "rolled, and counts them in late_flows_dropped",
                    (m.lateness, pipeline.__name__, name)))
                dropped.append(name)
    if config.ingest_fused == "on" and not host_sketch:
        # "on" is a hard requirement everywhere, not just inside the
        # pipeline constructor: any selection-level fallback above
        # (non-fusable models, host grouping ineligible, fused=False)
        # would otherwise silently run the staged/device path under a
        # flag that documents "errors when it cannot serve"
        error = RuntimeError(
            "ingest_fused='on' but the host sketch pipeline was "
            "not selected — it needs a fusable model set and "
            "host-grouped pre-aggregation (CPU backend or "
            "-processor.hostassist on)")
        return Choice(hh_sketch=hh_sketch, words=tuple(words), error=error)
    # Pipelined ingest: a group thread prepares batch N+1 while the
    # worker applies batch N, and a background flusher takes window
    # extraction + sink writes off the hot path. Only a pipeline with
    # the prepare/apply split can; the others keep the serial loop
    # (their overlap comes from jax async dispatch).
    pipelined = False
    if (config.ingest_mode == "pipelined" and prefetched is not None
            and pipeline is not None and pipeline.has_prepare_split):
        if prefetched:
            pipelined = True
        else:
            # prefetch=0 leaves the raw consumer unwrapped; moving its
            # poll() onto the group thread while commit() stays on the
            # worker's would hit a non-thread-safe Kafka client from
            # two threads. The PrefetchConsumer wrap is what serializes
            # all client access on its feed thread.
            words.append((INFO, "ingest pipelined mode needs the prefetch "
                                "wrap (feed.prefetch > 0); using the "
                                "serial path", ()))
    feeds = pipeline is not None and pipeline.feeds_audit
    sketches = hh_sketch != "none"
    if (config.obs_audit != "off" and models
            and not (feeds and sketches)):
        if not sketches:
            # nothing sketch-backed to audit (dense/exact models only):
            # flipping pipeline knobs would not change that
            words.append((INFO, "obs.audit=%s: no sketch-backed families "
                                "in the model set; nothing to audit",
                          (config.obs_audit,)))
        else:
            # the audit consumes the host-grouped pipelines' tables;
            # the device-sorted/per-model paths have nothing to feed it
            words.append((INFO, "obs.audit=%s needs the host-grouped "
                                "pipeline (CPU backend or "
                                "-processor.hostassist on); sketch "
                                "accuracy audit is off for this worker",
                          (config.obs_audit,)))
    return Choice(pipeline=pipeline, kwargs=kwargs, hh_sketch=hh_sketch,
                  pipelined=pipelined,
                  audit=config.obs_audit if feeds else "off",
                  lateness_dropped=tuple(dropped), words=tuple(words))
