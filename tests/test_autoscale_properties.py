"""Hypothesis property sweep for the event plane (§14).

A randomized invariant, separate from the deterministic suites so
environments without hypothesis still run those: the :class:`EventPlane`
delivery log is a pure function of (seed, subscriptions): replaying the
same seeded publish sequence into the same subscription set reproduces the
log exactly, payloads included.
"""

import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip only the property tests
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import EventPlane  # noqa: E402
from repro.core.eventplane import CLUSTER_TOPIC, SHARD_TOPIC  # noqa: E402

pytestmark = pytest.mark.shard


@settings(max_examples=50, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n_shards=st.integers(1, 5),
    n_events=st.integers(1, 80),
    patterns=st.lists(
        st.sampled_from(
            [
                (SHARD_TOPIC, "*"),
                (SHARD_TOPIC, 0),
                (SHARD_TOPIC, 2),
                (CLUSTER_TOPIC,),
                (CLUSTER_TOPIC, "*"),  # wrong arity: matches nothing
            ]
        ),
        min_size=0,
        max_size=6,
    ),
)
def test_delivery_log_pure_function_of_seed_and_subscriptions(
    seed, n_shards, n_events, patterns
):
    """Two buses with the same subscription list, fed the same seeded
    publish sequence, produce identical delivery logs and identical
    per-subscriber event streams — delivery order is never a function of
    anything but (seed, subscriptions)."""

    def build():
        bus = EventPlane()
        seen = [[] for _ in patterns]
        for sink, pattern in zip(seen, patterns):
            bus.subscribe(
                pattern,
                lambda ev, sink=sink: sink.append(
                    (ev.seq, ev.topic, ev.window, dict(ev.payload))
                ),
            )
        rng = np.random.default_rng(seed)
        for i in range(n_events):
            k = int(rng.integers(0, n_shards + 1))
            topic = (SHARD_TOPIC, k) if k < n_shards else (CLUSTER_TOPIC,)
            bus.publish(topic, i, float(i), float(i + 1),
                        {"n_done": int(rng.integers(0, 100))})
        return bus, seen

    bus_a, seen_a = build()
    bus_b, seen_b = build()
    assert bus_a.log == bus_b.log
    assert seen_a == seen_b
    assert (bus_a.published, bus_a.delivered) == (bus_b.published, bus_b.delivered)
    # the log is exactly the per-subscriber streams, interleaved in seq
    # order with registration order breaking ties
    rebuilt = [
        (seq, topic, window, sub_id)
        for sub_id, stream in enumerate(seen_a)
        for (seq, topic, window, _payload) in stream
    ]
    rebuilt.sort(key=lambda r: (r[0], r[3]))
    assert rebuilt == bus_a.log
