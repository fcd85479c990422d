"""Regression + property tests for the fault-tolerance seed.

Two seed bugs fixed in the elastic-fleet PR are pinned here:

* ``regenerate_straggler_bubbles`` cascaded: iterating (queue, parent)
  pairs bottom-up re-moved freshly-pushed tasks at every higher pair, so
  anything on a straggler's local queue shot straight to the global list
  (and was counted once per hop).  The paper's §3.3.3 regeneration move is
  exactly ONE level up — wide enough for healthy siblings to steal, narrow
  enough to keep affinity.

* ``FleetSpec.alive_shape`` subtracted every dead host's data column
  fleet-wide, as if a host loss in pod 0 destroyed the same column in
  every other pod.  The survivor mesh must instead be the largest
  fully-alive rectangle — dropping a badly-wounded pod entirely can keep
  far more of the fleet.
"""

import itertools

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import BubbleScheduler, bubble, novascale_16, thread
from repro.distributed.fault_tolerance import (FleetSpec,
                                               regenerate_straggler_bubbles)


class TestStragglerRegeneration:
    def test_moves_exactly_one_level(self):
        """Seed regression: a task on the straggler's cpu queue must land on
        its NODE queue (one level up), not cascade to the global list; a
        task already on the node queue moves to the machine.  The seed
        cascaded both to global and returned moved == 3."""
        sched = BubbleScheduler(novascale_16())
        a, b = bubble(thread(5.0)), bubble(thread(5.0))
        cpu0 = sched.topo.cpus[0]
        node0 = sched.topo.components("node")[0]
        q_cpu0 = sched.queues.queue_of(cpu0)
        q_node0 = sched.queues.queue_of(node0)
        q_cpu0.push(a)
        q_node0.push(b)
        moved = regenerate_straggler_bubbles(sched, [0])
        assert moved == 2
        assert list(q_node0.tasks) == [a]
        assert list(sched.queues.global_queue().tasks) == [b]
        assert len(q_cpu0) == 0

    def test_shared_queues_drained_once(self):
        """Two stragglers under the same node share every queue above the
        cpu level; the shared queues must be planned once, so the count
        matches the number of distinct tasks moved."""
        sched = BubbleScheduler(novascale_16())
        node0 = sched.topo.components("node")[0]
        sched.queues.queue_of(node0).push(bubble(thread(2.0)))
        cpus = [leaf.cpu for leaf in node0.leaves()][:2]
        moved = regenerate_straggler_bubbles(sched, cpus)
        assert moved == 1
        assert len(sched.queues.global_queue()) == 1

    def test_empty_chain_is_noop(self):
        sched = BubbleScheduler(novascale_16())
        assert regenerate_straggler_bubbles(sched, [0, 1, 2]) == 0


def brute_best(spec: FleetSpec):
    """Largest fully-alive rectangle by exhaustive pod-subset search."""
    alive = [p for p in range(spec.pods) if p not in spec.dead_pods]
    dead_cols = {p: {d for q, d in spec.dead_hosts if q == p}
                 for p in alive}
    best = None
    for r in range(1, len(alive) + 1):
        for keep in itertools.combinations(alive, r):
            cols = spec.data - len(set().union(*(dead_cols[p] for p in keep)))
            if cols <= 0:
                continue
            key = (r * cols, r)
            if best is None or key > best[0]:
                best = (key, r, cols)
    return None if best is None else (best[1], best[2])


class TestAliveShape:
    def test_wounded_pod_dropped_not_projected(self):
        """Seed regression: three dead hosts in pod 0 must cost pod 0, not
        three data columns of every pod.  Seed answered (4, 1, 2) — 8
        devices; the largest survivor rectangle is (3, 4, 2) — 24."""
        spec = FleetSpec(pods=4, data=4, model=2,
                         dead_hosts=frozenset({(0, 0), (0, 1), (0, 2)}))
        assert spec.alive_shape() == (3, 4, 2)
        assert spec.alive_axes() == ("pod", "data", "model")

    def test_single_dead_host_keeps_column_choice(self):
        # one dead host: keeping the pod costs a column fleet-wide (2x3),
        # dropping the pod keeps all columns for the survivor (1x4) —
        # the rectangle 2x3 wins
        spec = FleetSpec(pods=2, data=4, model=2,
                         dead_hosts=frozenset({(0, 1)}))
        assert spec.alive_shape() == (2, 3, 2)

    def test_dead_host_in_dead_pod_ignored(self):
        spec = FleetSpec(pods=2, data=4, model=2,
                         dead_pods=frozenset({1}),
                         dead_hosts=frozenset({(1, 0), (1, 1), (1, 2)}))
        assert spec.alive_shape() == (4, 2)
        assert spec.alive_axes() == ("data", "model")

    def test_exhausted_raises(self):
        import pytest
        spec = FleetSpec(pods=1, data=2, model=1,
                         dead_hosts=frozenset({(0, 0), (0, 1)}))
        with pytest.raises(RuntimeError):
            spec.alive_shape()

    @settings(max_examples=60)
    @given(pods=st.integers(min_value=1, max_value=4),
           data=st.integers(min_value=1, max_value=4),
           kills=st.integers(min_value=0, max_value=6),
           seed=st.integers(min_value=0, max_value=999))
    def test_matches_bruteforce_rectangle(self, pods, data, kills, seed):
        import numpy as np
        rng = np.random.default_rng(seed)
        cells = [(p, d) for p in range(pods) for d in range(data)]
        idx = rng.permutation(len(cells))[:min(kills, len(cells))]
        dead = frozenset(cells[i] for i in idx)
        spec = FleetSpec(pods=pods, data=data, model=2, dead_hosts=dead)
        want = brute_best(spec)
        if want is None:
            import pytest
            with pytest.raises(RuntimeError):
                spec._survivor_grid()
        else:
            assert spec._survivor_grid() == want
