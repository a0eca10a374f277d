"""Consumer: bus -> decoded FlowBatch with offset bookkeeping.

Offsets are committed explicitly by the caller AFTER its downstream flush —
at-least-once delivery, fixing the reference inserter's loss window (it
marks offsets per message before the batch hits the database,
ref: inserter/inserter.go:188 vs the flush at :161-163).
"""

from __future__ import annotations

from typing import Optional

from ..obs.trace import TRACER, next_chunk_id
from ..schema import wire
from ..schema.batch import FlowBatch
from .bus import InProcessBus


class Consumer:
    """Single-group consumer over all partitions of a topic.

    A real deployment runs one consumer per partition subset (the sarama
    consumer-group model); here one instance may own several partitions and
    polls them round-robin.
    """

    def __init__(self, bus: InProcessBus, topic: str = "flows",
                 group: str = "tpu-processor", fixedlen: bool = False,
                 partitions: Optional[list[int]] = None):
        self.bus = bus
        self.topic = topic
        self.group = group
        self.fixedlen = fixedlen
        self.partitions = (
            partitions
            if partitions is not None
            else list(range(bus.partitions(topic)))
        )
        # next offset to READ per partition (resumes from the last commit)
        self.positions = {
            p: bus.committed(group, topic, p) for p in self.partitions
        }
        self._rr_idx = 0

    def poll(self, max_messages: int = 8192) -> Optional[FlowBatch]:
        """Fetch up to max_messages across owned partitions and decode into
        one batch per partition (offsets stay contiguous). Returns None when
        fully caught up.

        Length-prefixed topics ride the bus's span fetch: the bulk decoder
        wants the frame concatenation anyway, so the per-message object
        path (one BusMessage per flow) is skipped entirely — it was the
        dominant consume-side cost at high rates."""
        for p in self._rotation():
            # the flowtrace chunk id: minted before the fetch, so that a
            # chunk's "fetch", "decode" and "apply" carry one id (a
            # fetch that finds nothing spends one)
            chunk = next_chunk_id()
            if self.fixedlen:
                with TRACER.span("fetch", chunk=chunk, partition=p,
                                 rows=0) as fetched:
                    span = self.bus.fetch_span(
                        self.topic, p, self.positions[p], max_messages)
                    if span is not None:
                        fetched["rows"] = span[2] - span[1] + 1
                if span is None:
                    continue
                data, first, last, produced = span
                batch = self._traced_decode(FlowBatch.from_wire, data, p,
                                            chunk)
                batch.first_offset = first
                batch.last_offset = last
                batch.produced_at = produced
                self.positions[p] = last + 1
                return batch
            with TRACER.span("fetch", chunk=chunk, partition=p,
                             rows=0) as fetched:
                msgs = self.bus.fetch(self.topic, p, self.positions[p],
                                      max_messages)
                fetched["rows"] = len(msgs)
            if not msgs:
                continue
            batch = self._traced_decode(self._decode, msgs, p, chunk)
            batch.first_offset = msgs[0].offset
            batch.last_offset = msgs[-1].offset
            # flowguard lag signal (the span path gets this inline; the
            # per-message path pays one extra stamp lookup)
            batch.produced_at = self.bus.produced_at(
                self.topic, p, msgs[0].offset)
            self.positions[p] = msgs[-1].offset + 1
            return batch
        return None

    @staticmethod
    def _traced_decode(decode, payload, partition: int,
                       chunk: int) -> FlowBatch:
        """Decode under a span that carries the chunk id its fetch
        minted."""
        with TRACER.span("decode", chunk=chunk,
                         partition=partition) as span:
            batch = decode(payload)
            span["rows"] = len(batch)
        batch.chunk_id = chunk
        batch.partition = partition
        return batch

    def _rotation(self):
        # rotate start partition so one hot partition cannot starve others
        if not self.partitions:
            return []
        first = self._rr_idx % len(self.partitions)
        self._rr_idx += 1
        return self.partitions[first:] + self.partitions[:first]

    def _decode(self, msgs) -> FlowBatch:
        # fixedlen never reaches here: poll()'s span fast path returns first
        return FlowBatch.from_messages(
            [wire.decode_message(m.value) for m in msgs]
        )

    def commit(self, partition: int, next_offset: int) -> None:
        """Call after downstream flush/snapshot covers offsets < next_offset."""
        self.bus.commit(self.group, self.topic, partition, next_offset)

    def committed(self, partition: int) -> int:
        return self.bus.committed(self.group, self.topic, partition)

    def lag(self) -> int:
        return self.bus.lag(self.group, self.topic)
