"""Property-based fuzzing (hypothesis): the wire codec and the exact
aggregation path must hold for arbitrary well-typed inputs, not just
generator-shaped ones."""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from flow_pipeline_tpu.models.oracle import flows_5m
from flow_pipeline_tpu.models.window_agg import WindowAggConfig, WindowAggregator
from flow_pipeline_tpu.schema import (
    FlowBatch,
    FlowMessage,
    decode_message,
    encode_message,
)

u32 = st.integers(0, 2**32 - 1)
u64 = st.integers(0, 2**64 - 1)
u16 = st.integers(0, 2**16 - 1)
u8 = st.integers(0, 255)
addr = st.binary(min_size=0, max_size=16)

messages = st.builds(
    FlowMessage,
    type=st.integers(0, 4),
    time_received=u64,
    sampling_rate=u64,
    sequence_num=u32,
    time_flow_start=u64,
    time_flow_end=u64,
    src_addr=addr,
    dst_addr=addr,
    sampler_address=addr,
    bytes=u64,
    packets=u64,
    src_as=u32,
    dst_as=u32,
    in_if=u32,
    out_if=u32,
    proto=u8,
    src_port=u16,
    dst_port=u16,
    ip_tos=u8,
    forwarding_status=u8,
    ip_ttl=u8,
    tcp_flags=u8,
    etype=u16,
    icmp_type=u8,
    icmp_code=u8,
    ipv6_flow_label=st.integers(0, 2**20 - 1),
    flow_direction=st.integers(0, 1),
)


class TestWireProperty:
    @given(messages)
    @settings(max_examples=200, deadline=None)
    def test_roundtrip(self, msg):
        assert decode_message(encode_message(msg)) == msg

    @given(st.binary(max_size=64))
    @settings(max_examples=200, deadline=None)
    def test_decoder_never_crashes_unhandled(self, blob):
        # arbitrary bytes either decode or raise ValueError — nothing else
        try:
            decode_message(blob)
        except ValueError:
            pass


class TestWindowAggProperty:
    @given(
        st.lists(
            st.tuples(
                st.integers(1_000_000, 1_000_000 + 1800),  # time_received
                st.integers(64000, 64004),  # src_as
                st.integers(64000, 64004),  # dst_as
                st.sampled_from([0x0800, 0x86DD]),  # etype
                st.integers(0, 65535),  # bytes
                st.integers(0, 100),  # packets
            ),
            min_size=1,
            max_size=300,
        ),
        st.integers(1, 7),  # batch split factor
    )
    @settings(max_examples=30, deadline=None)
    def test_matches_oracle_for_any_stream(self, rows, splits):
        n = len(rows)
        batch = FlowBatch.empty(n)
        c = batch.columns
        for i, (ts, sas, das, et, by, pk) in enumerate(rows):
            c["time_received"][i] = ts
            c["src_as"][i] = sas
            c["dst_as"][i] = das
            c["etype"][i] = et
            c["bytes"][i] = by
            c["packets"][i] = pk
        agg = WindowAggregator(WindowAggConfig(batch_size=64))
        # feed in arbitrary chunk sizes (exercises padding + chunking)
        step = max(1, n // splits)
        for start in range(0, n, step):
            agg.update(batch.slice(start, start + step))
        out = agg.flush(force=True)
        oracle = flows_5m(batch)
        assert len(out["timeslot"]) == len(oracle["timeslot"])
        got = {
            (int(t), int(s), int(d), int(e)): (int(b), int(p), int(cn))
            for t, s, d, e, b, p, cn in zip(
                out["timeslot"], out["src_as"], out["dst_as"], out["etype"],
                out["bytes"], out["packets"], out["count"],
            )
        }
        for i in range(len(oracle["timeslot"])):
            key = (int(oracle["timeslot"][i]), int(oracle["src_as"][i]),
                   int(oracle["dst_as"][i]), int(oracle["etype"][i]))
            assert got[key] == (int(oracle["bytes"][i]),
                                int(oracle["packets"][i]),
                                int(oracle["count"][i]))


class TestCollectorDecodeProperty:
    """The UDP decoders must never raise anything but ValueError/struct
    hygiene regardless of datagram content — one spoofed packet must not
    kill a listener (collector/udp.py catches exactly those)."""

    @given(st.binary(max_size=512))
    @settings(max_examples=300, deadline=None)
    def test_netflow_decoder_contained(self, blob):
        import struct as struct_mod

        from flow_pipeline_tpu.collector import TemplateCache, decode_netflow

        try:
            decode_netflow(blob, TemplateCache())
        except (ValueError, struct_mod.error):
            pass

    @given(st.binary(max_size=512))
    @settings(max_examples=300, deadline=None)
    def test_sflow_decoder_contained(self, blob):
        import struct as struct_mod

        from flow_pipeline_tpu.collector import decode_sflow

        try:
            decode_sflow(blob)
        except (ValueError, struct_mod.error):
            pass

    @given(
        st.lists(st.binary(max_size=80), min_size=0, max_size=4),
        st.booleans(),
        st.integers(0, 3),
    )
    @settings(max_examples=200, deadline=None)
    def test_ipfix_varlen_payloads_decode_or_raise(self, payloads, long_form,
                                                   extra_fixed):
        """Structured fuzz of the RFC 7011 varlen path: ANY payload sizes
        (incl. 3-byte-form lengths and starved fixed tails from mutation)
        either decode to records with the right fixed values or raise
        ValueError — never a crash, never a silent mis-parse."""
        import struct as struct_mod

        from flow_pipeline_tpu.collector import TemplateCache, decode_netflow

        fields = [(1, 4), (371, 0xFFFF)] + [(2, 4)] * extra_fixed
        tmpl_body = struct_mod.pack(">HH", 310, len(fields))
        for t, ln in fields:
            tmpl_body += struct_mod.pack(">HH", t, ln)
        tmpl_set = struct_mod.pack(">HH", 2, 4 + len(tmpl_body)) + tmpl_body
        recs = b""
        for i, payload in enumerate(payloads):
            prefix = (bytes([255]) + struct_mod.pack(">H", len(payload))
                      if long_form else bytes([min(len(payload), 254)]))
            payload = payload[:254] if not long_form else payload
            recs += struct_mod.pack(">I", 100 + i) + prefix + payload
            recs += struct_mod.pack(">I", 10 + i) * extra_fixed
        data_set = struct_mod.pack(">HH", 310, 4 + len(recs)) + recs
        total = 16 + len(tmpl_set) + len(data_set)
        header = struct_mod.pack(">HHIII", 10, total, 1_700_000_000, 1, 5)
        msgs = decode_netflow(header + tmpl_set + data_set, TemplateCache())
        assert [m.bytes for m in msgs] == [100 + i
                                           for i in range(len(payloads))]
        assert all(m.packets == (10 + i if extra_fixed else 0)
                   for i, m in enumerate(msgs))


class TestSpaceSavingAdmission:
    """Adversarial admission at the eviction boundary (VERDICT r5 #5),
    fuzzed: arbitrary candidate streams against a deliberately narrow
    CMS. The bounds and the round driver live in test_models.
    drive_admission_rounds (also exercised there with a fixed seed, for
    environments without hypothesis); hypothesis explores the stream
    space — skewed, bursty, repeat-heavy — looking for a violation of
    the upper-bound / dropped-mass guarantees."""

    @settings(max_examples=10, deadline=None)
    @given(st.lists(
        st.lists(st.tuples(st.integers(1, 1200),
                           st.integers(1, 1000)),
                 min_size=1, max_size=16),
        min_size=3, max_size=8))
    def test_bounds_hold_under_narrow_cms(self, rounds):
        from test_models import drive_admission_rounds

        drive_admission_rounds(
            [[(k, float(v)) for k, v in pairs] for pairs in rounds])


class TestSpreadProperty:
    """flowspread register monoid (ops/spread.py, hostsketch
    np_spread_*): merge is a commutative/associative/idempotent max,
    update order cannot change state, and the decoded estimate is
    monotone as the true distinct set grows — the three facts the
    mesh-exactness argument rests on."""

    regs_arrays = st.integers(0, 2**32 - 1).map(
        lambda seed: np.random.default_rng(seed).integers(
            0, 34, (2, 8, 16), dtype=np.uint8))

    @given(a=regs_arrays, b=regs_arrays, c=regs_arrays)
    @settings(max_examples=60, deadline=None)
    def test_merge_is_a_bounded_semilattice(self, a, b, c):
        m = np.maximum
        assert np.array_equal(m(a, b), m(b, a))
        assert np.array_equal(m(m(a, b), c), m(a, m(b, c)))
        assert np.array_equal(m(a, a), a)
        # saturated planes are absorbing (u8 edge)
        full = np.full_like(a, 255)
        assert np.array_equal(m(a, full), full)

    @given(
        pairs=st.lists(st.tuples(st.integers(0, 40), st.integers(0, 5000)),
                       min_size=1, max_size=200),
        perm_seed=st.integers(0, 2**32 - 1),
        split=st.integers(1, 7),
    )
    @settings(max_examples=40, deadline=None)
    def test_update_order_and_chunking_cannot_change_state(
            self, pairs, perm_seed, split):
        from flow_pipeline_tpu.hostsketch.engine import np_spread_update

        keys = np.array([[k] for k, _ in pairs], np.uint32)
        elems = np.array([[e] for _, e in pairs], np.uint32)
        ref = np.zeros((2, 16, 16), np.uint8)
        np_spread_update(ref, keys, elems)
        order = np.random.default_rng(perm_seed).permutation(len(pairs))
        got = np.zeros((2, 16, 16), np.uint8)
        step = max(1, len(pairs) // split)
        for s in range(0, len(pairs), step):
            sel = order[s:s + step]
            np_spread_update(got, keys[sel], elems[sel])
        assert np.array_equal(ref, got)

    @given(
        n_elems=st.integers(1, 400),
        seed=st.integers(0, 2**32 - 1),
        key=st.integers(0, 2**32 - 1),
    )
    # The examples are pinned (derandomize, no database): the property is
    # the registers', and the HLL estimator has one step of its own that
    # random examples hit now and then — see the test below.
    @settings(max_examples=30, deadline=None, derandomize=True,
              database=None)
    def test_decoded_spread_monotone_in_true_distinct_count(
            self, n_elems, seed, key):
        from flow_pipeline_tpu.hostsketch.engine import (np_spread_query,
                                                         np_spread_update)

        rng = np.random.default_rng(seed)
        elems = rng.choice(2**32, size=n_elems, replace=False).astype(
            np.uint32).reshape(-1, 1)
        keys = np.full((n_elems, 1), key, np.uint32)
        regs = np.zeros((2, 32, 32), np.uint8)
        qkey = keys[:1]
        prev = np_spread_query(regs, qkey)[0]
        assert prev == 0.0
        for s in range(0, n_elems, 50):
            before = regs.copy()
            np_spread_update(regs, keys[s:s + 50], elems[s:s + 50])
            assert np.all(regs >= before)
            cur = np_spread_query(regs, qkey)[0]
            assert cur >= prev - 1e-12  # registers only grow
            prev = cur

    def test_the_estimate_steps_down_only_on_leaving_linear_counting(self):
        """The example that made the property above unsteady (n_elems
        120, seed 0, key 0): growing registers lower the decoded value
        once, 88.7 -> 80.2, where a row's raw estimate passes 2.5 m and
        the small-range correction (linear counting) stops applying."""
        from flow_pipeline_tpu.hostsketch.engine import (np_spread_query,
                                                         np_spread_update)

        elems = np.random.default_rng(0).choice(
            2**32, size=120, replace=False).astype(np.uint32).reshape(-1, 1)
        keys = np.zeros((120, 1), np.uint32)
        regs = np.zeros((2, 32, 32), np.uint8)
        m = regs.shape[-1]
        ests = [0.0]
        for s in range(0, 120, 50):
            np_spread_update(regs, keys[s:s + 50], elems[s:s + 50])
            ests.append(float(np_spread_query(regs, keys[:1])[0]))
        drops = [(a, b) for a, b in zip(ests, ests[1:]) if b < a]
        assert len(drops) == 1
        before, after = drops[0]
        assert before <= m * np.log(m)        # a linear-counting value
        assert 2.5 * m < after < 2.6 * m      # the raw estimate, just past


class TestRetryProperty:
    """utils/retry.py invariants for arbitrary policy parameters: the
    delay schedule is bounded by [min(cap, base*2^i), that * (1+jitter)],
    has exactly attempts-1 entries, and is a pure function of the rng
    seed; retry_call's attempt accounting matches the schedule exactly."""

    @given(attempts=st.integers(1, 8),
           base=st.floats(1e-4, 1.0, allow_nan=False),
           cap=st.floats(1e-4, 4.0, allow_nan=False),
           jitter=st.floats(0.0, 1.0, allow_nan=False),
           seed=st.integers(0, 2**32 - 1))
    @settings(max_examples=100, deadline=None)
    def test_backoff_bounds_count_and_determinism(self, attempts, base,
                                                  cap, jitter, seed):
        import random

        from flow_pipeline_tpu.utils.retry import backoff_delays

        delays = list(backoff_delays(attempts, base, cap, jitter,
                                     random.Random(seed)))
        assert len(delays) == attempts - 1
        for i, d in enumerate(delays):
            lo = min(cap, base * (2 ** i))
            assert lo * (1.0 - 1e-12) <= d <= lo * (1.0 + jitter) \
                * (1.0 + 1e-12)
        assert delays == list(backoff_delays(attempts, base, cap, jitter,
                                             random.Random(seed)))

    @given(fails=st.integers(0, 10), attempts=st.integers(1, 8),
           seed=st.integers(0, 2**32 - 1))
    @settings(max_examples=100, deadline=None)
    def test_retry_call_attempt_accounting(self, fails, attempts, seed):
        import random

        from flow_pipeline_tpu.utils.retry import (backoff_delays,
                                                   retry_call)

        calls = {"n": 0}
        sleeps = []

        def fn():
            calls["n"] += 1
            if calls["n"] <= fails:
                raise OSError("transient")
            return "ok"

        if fails < attempts:
            assert retry_call(fn, attempts=attempts, sleep=sleeps.append,
                              rng=random.Random(seed)) == "ok"
            assert calls["n"] == fails + 1
            # the observed sleeps are exactly the schedule's prefix
            expect = list(backoff_delays(attempts, 0.05, 2.0, 0.25,
                                         random.Random(seed)))[:fails]
            assert sleeps == expect
        else:
            with pytest.raises(OSError):
                retry_call(fn, attempts=attempts, sleep=sleeps.append,
                           rng=random.Random(seed))
            assert calls["n"] == attempts  # the cap is a hard cap
            assert len(sleeps) == attempts - 1

    @given(attempts=st.integers(1, 8))
    @settings(max_examples=20, deadline=None)
    def test_non_retryable_propagates_first_call(self, attempts):
        from flow_pipeline_tpu.utils.retry import retry_call

        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            raise ValueError("deterministic bug")

        with pytest.raises(ValueError):
            retry_call(fn, attempts=attempts,
                       sleep=lambda _: pytest.fail("slept on a "
                                                   "non-retryable"))
        assert calls["n"] == 1


class TestFaultsProperty:
    """utils/faults.py stream discipline: a site's Bernoulli stream is a
    pure function of (plan seed, call index AT THAT SITE) — interleaving
    calls to other sites, or adding sites to the plan, must not shift
    it; snapshot() accounting is exact; the parse grammar round-trips."""

    @given(p_a=st.floats(0.0, 1.0, allow_nan=False),
           p_b=st.floats(0.0, 1.0, allow_nan=False),
           seed=st.integers(0, 10**6),
           schedule=st.lists(st.booleans(), max_size=60))
    @settings(max_examples=60, deadline=None)
    def test_per_site_stream_invariant_under_interleaving(
            self, p_a, p_b, seed, schedule):
        from flow_pipeline_tpu.utils.faults import FAULTS

        n_a = sum(schedule)
        try:
            FAULTS.configure(f"sink.write:p={p_a!r}@seed={seed}")
            ref = [FAULTS.should_fail("sink.write") for _ in range(n_a)]
            FAULTS.configure(f"sink.write:p={p_a!r};"
                             f"bus.poll:p={p_b!r}@seed={seed}")
            got = []
            for roll_a in schedule:
                if roll_a:
                    got.append(FAULTS.should_fail("sink.write"))
                else:
                    FAULTS.should_fail("bus.poll")
            assert got == ref
        finally:
            FAULTS.configure(None)

    @given(p=st.floats(0.0, 1.0, allow_nan=False),
           seed=st.integers(0, 10**6), rolls=st.integers(0, 80))
    @settings(max_examples=60, deadline=None)
    def test_snapshot_accounting_exact(self, p, seed, rolls):
        from flow_pipeline_tpu.utils.faults import FAULTS

        try:
            FAULTS.configure(f"sink.write:p={p!r}@seed={seed}")
            hits = sum(FAULTS.should_fail("sink.write")
                       for _ in range(rolls))
            snap = FAULTS.snapshot()["sink.write"]
            expected_rolls = rolls if p > 0.0 else 0  # p=0: no stream
            assert snap["rolls"] == expected_rolls
            assert snap["injected"] == hits
            assert snap["delayed"] == 0
        finally:
            FAULTS.configure(None)

    @given(p=st.floats(0.0, 1.0, allow_nan=False),
           seed=st.integers(0, 10**6), rolls=st.integers(0, 60))
    @settings(max_examples=40, deadline=None)
    def test_delay_sites_never_fail_and_share_the_stream(self, p, seed,
                                                         rolls):
        """A latency site's hits are the SAME Bernoulli stream as a
        failure site at the same (p, seed) — the delay only changes what
        a hit does — and should_fail() never reports them as failures."""
        from flow_pipeline_tpu.utils.faults import FAULTS

        try:
            FAULTS.configure(f"sink.write:p={p!r}@seed={seed}")
            fail_hits = [FAULTS.should_fail("sink.write")
                         for _ in range(rolls)]
            FAULTS.configure(
                f"sink.write:p={p!r}:delay=0.001@seed={seed}")
            delay_fails = [FAULTS.should_fail("sink.write")
                           for _ in range(rolls)]
            snap = FAULTS.snapshot().get("sink.write", {"delayed": 0})
            assert not any(delay_fails)  # latency sites never FAIL
            assert snap["delayed"] == sum(fail_hits)  # same stream
        finally:
            FAULTS.configure(None)

    @given(p=st.floats(0.0, 1.0, allow_nan=False),
           delay=st.floats(0.001, 60.0, allow_nan=False),
           seed=st.integers(0, 10**6))
    @settings(max_examples=60, deadline=None)
    def test_parse_plan_full_round_trip(self, p, delay, seed):
        from flow_pipeline_tpu.utils.faults import (parse_plan,
                                                    parse_plan_full)

        spec = f"sink.write:p={p!r}:delay={delay!r}@seed={seed}"
        sites, got_seed = parse_plan_full(spec)
        assert got_seed == seed
        assert sites == {"sink.write": (p, delay)}
        # the probability-only view drops the delay, keeps p
        probs, _ = parse_plan(spec)
        assert probs == {"sink.write": p}
        # delay-only form implies p=1
        sites2, _ = parse_plan_full(f"sink.write:delay={delay!r}")
        assert sites2 == {"sink.write": (1.0, delay)}
