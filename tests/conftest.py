"""Test configuration.

Tests run on CPU with 8 virtual devices standing in for a v5e-8 (SURVEY.md §4:
multi-chip tests on CPU via xla_force_host_platform_device_count). Must be set
before jax is imported anywhere.
"""

import os

# Tests run on the CPU (utils.platform.force_cpu: the env var for child
# processes, the config for this one). XLA_FLAGS must be set before import.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from flow_pipeline_tpu.utils.platform import force_cpu

force_cpu()

import threading

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


# Worker pipeline threads (PipelinedExecutor / PrefetchConsumer /
# AsyncFlusher) are daemons, so a test that drains a worker with
# run_once() and never calls finalize() leaks them silently — and a
# leaked prefetch poller keeps hitting the bus.poll FAULTS seam
# forever, polluting any later test that arms a fault plan on it.
_PIPELINE_THREADS = ("feed-prefetch", "ingest-group", "ingest-flush")


@pytest.fixture(autouse=True, scope="module")
def _reap_leaked_pipeline_threads():
    """Signal pipeline threads leaked by this module to exit."""
    yield
    for t in threading.enumerate():
        if t.name not in _PIPELINE_THREADS or not t.is_alive():
            continue
        # each thread target is a bound _run method; its owner exposes
        # the same stop signal stop() uses, minus the join/drain — a
        # leaked thread has nothing pending worth draining
        owner = getattr(getattr(t, "_target", None), "__self__", None)
        stop = getattr(owner, "_stop", None)
        if stop is not None:
            stop.set()
        jobs = getattr(owner, "_jobs", None)
        if jobs is not None:
            jobs.put(None)  # wake a flusher blocked on queue.get()
