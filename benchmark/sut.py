"""The system under test: ``processor`` through its own entry point.

``Sut.serve()`` calls ``flow_pipeline_tpu.cli.processor_main`` with the
configuration file's flags, on the calling (main) thread, exactly as
``python -m flow_pipeline_tpu.cli processor ...`` would, with
``-listen.feed`` as the broker-less transport. Around it, from outside:

- the ``InProcessBus`` that ``processor_main`` creates is caught as it is
  constructed and given the configuration's partition count, so that the
  benchmark's generator can hand frames straight to it (the producer side
  of a topic), on a thread of this process;
- spans are wrapped round the calls into each layer (``HOOKS`` below, and
  any further ``spans`` a configuration file lists);
- ``StreamWorker.run_once`` checks a stop flag between batches and ends
  the loop the way an operator's interrupt does (``KeyboardInterrupt`` ->
  ``processor_main`` drains through ``worker.finalize()``);
- ``StreamWorker.finalize`` is followed by ``after_finalize`` while the
  query surface is still up.

Nothing of the program is edited or replaced; every wrapper calls the
method it wraps.
"""

from __future__ import annotations

import importlib
import threading

from .spans import SpanLog


def _arg(args: tuple, kwargs: dict, at: int, name: str):
    return args[at] if len(args) > at else kwargs[name]


# (module, class, method, span name, what to keep with the span); a fetch
# keeps (partition, first offset, flows), a commit (partition, next offset)
HOOKS = [
    ("flow_pipeline_tpu.transport.bus", "InProcessBus", "fetch_span",
     "bus_fetch", lambda a, k, r: None if r is None else (
         _arg(a, k, 2, "partition"), r[1], r[2] - r[1] + 1)),
    ("flow_pipeline_tpu.transport.bus", "InProcessBus", "commit",
     "bus_commit", lambda a, k, r: (_arg(a, k, 3, "partition"),
                                    _arg(a, k, 4, "next_offset"))),
    ("flow_pipeline_tpu.schema.batch", "FlowBatch", "from_wire",
     "decode", lambda a, k, r: 0 if r is None else len(r)),
    ("flow_pipeline_tpu.engine.worker", "StreamWorker", "_process",
     "process", lambda a, k, r: len(a[1])),
    ("flow_pipeline_tpu.engine.worker", "StreamWorker", "_flush_closed",
     "flush_closed", lambda a, k, r: bool(r)),
    ("flow_pipeline_tpu.engine.worker", "StreamWorker", "_write_rows",
     "sink_write", lambda a, k, r: a[1]),
    ("flow_pipeline_tpu.engine.worker", "StreamWorker",
     "snapshot_and_commit", "snapshot_and_commit", None),
    ("flow_pipeline_tpu.serve.publisher", "WorkerServePublisher", "publish",
     "publish", lambda a, k, r: (r.version, r.flows_seen)),
]


class Sut:
    def __init__(self, spans: SpanLog, topic: str, partitions: int):
        self.spans = spans
        self.topic = topic
        self.partitions = partitions
        self.bus = None
        self.bus_ready = threading.Event()
        self.worker = None
        self.stop = threading.Event()
        self.after_finalize = None  # callable(worker), set by the run
        self._undo: list = []

    # ---- wiring -----------------------------------------------------------

    def install(self, extra_hooks=()) -> None:
        for mod, cls, attr, name, meta in list(HOOKS) + [
                (*h, None) for h in extra_hooks]:
            owner = getattr(importlib.import_module(mod), cls)
            self.spans.wrap(owner, attr, name, meta)
        from flow_pipeline_tpu.engine.worker import StreamWorker
        from flow_pipeline_tpu.transport.bus import InProcessBus

        sut = self
        bus_init = InProcessBus.__init__
        run, run_once, finalize = (StreamWorker.run, StreamWorker.run_once,
                                   StreamWorker.finalize)

        def init(bus, *a, **kw):
            bus_init(bus, *a, **kw)
            bus.create_topic(sut.topic, sut.partitions)
            sut.bus = bus
            sut.bus_ready.set()

        def run_(worker, *a, **kw):
            sut.worker = worker
            return run(worker, *a, **kw)

        def run_once_(worker):
            if sut.stop.is_set():
                raise KeyboardInterrupt  # the operator's interrupt
            return run_once(worker)

        def finalize_(worker):
            finalize(worker)
            if sut.after_finalize is not None:
                sut.after_finalize(worker)

        for owner, attr, new, old in (
                (InProcessBus, "__init__", init, bus_init),
                (StreamWorker, "run", run_, run),
                (StreamWorker, "run_once", run_once_, run_once),
                (StreamWorker, "finalize", finalize_, finalize)):
            setattr(owner, attr, new)
            self._undo.append((owner, attr, old))

    def uninstall(self) -> None:
        for owner, attr, old in reversed(self._undo):
            setattr(owner, attr, old)
        self._undo.clear()
        self.spans.restore()

    # ---- the program's entry point ----------------------------------------

    def serve(self, argv: list) -> int:
        """Blocks in ``processor_main`` until ``stop`` is set and the
        worker has drained."""
        from flow_pipeline_tpu import cli

        return cli.processor_main(argv)
