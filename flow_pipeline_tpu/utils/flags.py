"""Dotted-flag config system.

The reference configures everything through Go stdlib flags with dotted
names — ``-kafka.brokers``, ``-flush.dur``, ``-proto.fixedlen``,
``-loglevel`` (ref: inserter/inserter.go:26-42, mocker/mocker.go:15-23) —
and one env fallback ($POSTGRES_PASSWORD when -postgres.pass is unset,
ref: inserter/inserter.go:220-224). This module reproduces that exact
surface (single-dash long flags, ``-flag value`` and ``-flag=value``,
bools accepting bare ``-flag`` / ``-flag=false``) so compose command lines
written for the reference binaries carry over.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

# The single flag registry. Every flag any binary declares MUST be listed
# here (FlagSet's builders assert it; tools/flowlint's flag-registry rule
# additionally checks that every `-x.y` string literal in the repo names
# a registered flag and that every dotted flag is documented in
# README/docs — see docs/STATIC_ANALYSIS.md). A typo'd flag name in a
# bench harness or compose file otherwise parses fine and silently
# measures the wrong configuration.
KNOWN_FLAGS = frozenset({
    # common
    "loglevel", "kafka.topic", "kafka.brokers", "proto.fixedlen",
    # generator / mocker
    "produce.count", "produce.rate", "produce.seed", "produce.profile",
    "produce.batch", "produce.shard", "zipf.keys", "zipf.alpha",
    "zipf.spread", "out",
    # processor
    "processor.backend", "processor.batch", "processor.mesh",
    "processor.fused", "processor.hostassist",
    "model.flows5m", "model.talkers", "model.pairs", "model.ips",
    "model.ports", "model.ddos",
    "sketch.width", "sketch.prefilter", "sketch.admission",
    "sketch.capacity", "sketch.topk", "sketch.backend", "hh.sketch",
    # flowspread (models/spread.py) — distinct-count detectors
    "spread.enabled", "spread.depth", "spread.width", "spread.regs",
    "spread.capacity", "spread.topk",
    "window.lateness", "window.slide", "archive.raw", "feed.prefetch",
    "ingest.fused", "ingest.threads",
    "checkpoint.path", "flush.count", "metrics.addr", "sink", "in",
    "listen.feed", "query.addr", "obs.trace", "obs.audit",
    # flowchaos (utils/faults.py, sink/resilient.py, mesh/journal.py)
    "faults", "sink.retries", "sink.deadletter",
    # flowguard (guard/) — overload control + degradation ladder
    "guard.lag", "guard.max_level", "guard.serve_queue",
    "guard.serve_deadline",
    # flowtpu-replay (the dead-letter re-ingestion subcommand)
    "replay.dir", "replay.delete",
    # flowserve (serve/)
    "serve.addr", "serve.refresh", "serve.feed_bytes",
    # flowgate (gateway/)
    "gateway.listen", "gateway.upstream", "gateway.poll",
    "gateway.adopt-restart",
    # flowhistory (history/) — durable snapshot archive + time travel
    "history.dir", "history.keyframe", "history.retain",
    "history.upstream", "history.listen", "history.poll",
    # flowmesh (mesh/)
    "mesh.workers", "mesh.role", "mesh.coordinator", "mesh.id",
    "mesh.listen", "mesh.heartbeat", "mesh.journal",
    # meshscope lineage CLI (the `lineage` subcommand)
    "lineage.model", "lineage.slot", "lineage.raw",
    # inserter
    "postgres.dsn", "postgres.pass", "sqlite", "flush.dur",
    # topic admin
    "bus.partitions",
    # collector
    "listen.netflow", "listen.sflow", "run.seconds",
})


@dataclass
class Flag:
    name: str
    default: Any
    help: str
    parse: Callable[[str], Any]
    env: Optional[str] = None  # env var fallback when flag unset
    is_bool: bool = False


def _parse_bool(s: str) -> bool:
    if s.lower() in ("1", "true", "t", "yes"):
        return True
    if s.lower() in ("0", "false", "f", "no"):
        return False
    raise ValueError(f"invalid boolean {s!r}")


class FlagSet:
    def __init__(self, prog: str):
        self.prog = prog
        self._flags: dict[str, Flag] = {}
        self.values: dict[str, Any] = {}

    def _register(self, flag: Flag) -> None:
        if flag.name not in KNOWN_FLAGS:
            raise ValueError(
                f"flag -{flag.name} is not in utils.flags.KNOWN_FLAGS; "
                "add it to the registry (and document it — `make lint` "
                "enforces both)")
        self._flags[flag.name] = flag

    def string(self, name: str, default: str, help_: str, env: str | None = None):
        self._register(Flag(name, default, help_, str, env))
        return self

    def integer(self, name: str, default: int, help_: str):
        self._register(Flag(name, default, help_, int))
        return self

    def number(self, name: str, default: float, help_: str):
        self._register(Flag(name, default, help_, float))
        return self

    def boolean(self, name: str, default: bool, help_: str):
        self._register(Flag(name, default, help_, _parse_bool, is_bool=True))
        return self

    def usage(self) -> str:
        lines = [f"Usage of {self.prog}:"]
        for name in sorted(self._flags):
            f = self._flags[name]
            lines.append(f"  -{name} (default {f.default!r})\n        {f.help}")
        return "\n".join(lines)

    def parse(self, argv: Sequence[str]) -> dict[str, Any]:
        """Parse Go-style flags; raises SystemExit on -h/-help, ValueError on
        unknown or malformed flags."""
        vals = {}
        i = 0
        argv = list(argv)
        while i < len(argv):
            arg = argv[i]
            if not arg.startswith("-"):
                raise ValueError(f"unexpected positional argument {arg!r}")
            name = arg.lstrip("-")
            value = None
            if "=" in name:
                name, value = name.split("=", 1)
            if name in ("h", "help"):
                print(self.usage())
                raise SystemExit(0)
            flag = self._flags.get(name)
            if flag is None:
                raise ValueError(f"flag provided but not defined: -{name}\n{self.usage()}")
            if value is None:
                if flag.is_bool:
                    value = "true"  # bare -flag
                else:
                    i += 1
                    if i >= len(argv):
                        raise ValueError(f"flag -{name} needs a value")
                    value = argv[i]
            try:
                vals[name] = flag.parse(value)
            except ValueError as e:
                raise ValueError(f"invalid value for -{name}: {e}") from e
            i += 1
        for name, flag in self._flags.items():
            if name not in vals:
                if flag.env and os.environ.get(flag.env):
                    vals[name] = flag.parse(os.environ[flag.env])
                else:
                    vals[name] = flag.default
        self.values = vals
        return vals
