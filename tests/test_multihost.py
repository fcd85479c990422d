"""Property tests for the pod-sharded serving topology + HBM accounting.

Two ``hypothesis`` families:

* **topology** — over 1-4 pods x 1-4 hosts x ragged page/slot fanouts:
  slot conservation (every submitted slot is a schedulable leaf, no page
  group empty), ``levels_crossed`` symmetry between leaves, and
  steal-survey reachability (work parked on *any* slot's list can be
  stolen by *any* other slot, under both the free and the costed victim
  selection — a partitioned survey would starve whole shards);
* **HBM accounting** — random admit/park/steal/rebalance traffic against
  per-page-group budgets: the KV ledger never goes negative or above
  budget at any step, it always equals the sum of live slot reservations,
  and refused loot is always re-admitted somewhere (every request
  completes — no gang starves because a full group turned it away);
* **per-host execution determinism** — on every 1-4 pod x 1-4 host fleet,
  the host-sharded execution model (one ``decode_step`` per host batch,
  wave-batched prefill) produces bit-identical decode streams *and* step
  counts to the historical global batch: sharding execution is pure
  modeling, never scheduling.
"""

import numpy as np
import pytest

import hypothesis.strategies as st
from hypothesis import example, given, settings

from repro.core.bubble import thread
from repro.core.policies import StealPolicy
from repro.core.scheduler import ZERO_COST, StealCostModel
from repro.serving import (SERVE_COST, ServingEngine, StubModelBackend,
                           slots_topology)


@st.composite
def fleet(draw):
    """(pods, hosts, group, n_slots) with ragged splits everywhere."""
    pods = draw(st.integers(min_value=1, max_value=4))
    hosts = draw(st.integers(min_value=1, max_value=4))
    group = draw(st.integers(min_value=1, max_value=5))
    n_hosts = pods * hosts
    n_slots = draw(st.integers(min_value=n_hosts, max_value=n_hosts * 9))
    return pods, hosts, group, n_slots


# ---------------------------------------------------------------------------
# topology: conservation, symmetry, reachability
# ---------------------------------------------------------------------------

class TestFleetTopology:
    @settings(max_examples=40)
    @given(cfg=fleet())
    def test_slot_conservation(self, cfg):
        pods, hosts, group, n_slots = cfg
        topo = slots_topology(n_slots, group, hosts=hosts, pods=pods)
        assert topo.n_cpus == n_slots
        pages = topo.components("page")
        sizes = [len(p.children) for p in pages]
        assert sum(sizes) == n_slots
        assert min(sizes) >= 1                      # no empty page group
        assert all(s <= group for s in sizes)       # group is a ceiling
        # every host owns at least one page and sizes stay near-even
        host_level = "host" if pods * hosts > 1 else "batch"
        by_host = {}
        for p in pages:
            anc = p
            while anc.level.name != host_level:
                anc = anc.parent
            by_host.setdefault(anc.index, 0)
            by_host[anc.index] += len(p.children)
        assert len(by_host) == max(pods * hosts, 1)
        assert max(by_host.values()) - min(by_host.values()) <= 1

    @settings(max_examples=25)
    @given(cfg=fleet(), a=st.integers(min_value=0, max_value=10 ** 6),
           b=st.integers(min_value=0, max_value=10 ** 6))
    def test_levels_crossed_symmetry(self, cfg, a, b):
        pods, hosts, group, n_slots = cfg
        topo = slots_topology(n_slots, group, hosts=hosts, pods=pods)
        ca, cb = topo.cpus[a % n_slots], topo.cpus[b % n_slots]
        assert topo.levels_crossed(ca.cpu, cb) == \
            topo.levels_crossed(cb.cpu, ca)
        # the boundary level both directions price is the same one
        assert topo.crossing_level(ca.cpu, cb) == \
            topo.crossing_level(cb.cpu, ca)
        if ca is cb:
            assert topo.levels_crossed(ca.cpu, cb) == 0
            assert topo.crossing_level(ca.cpu, cb) is None

    @settings(max_examples=15, deadline=None)
    @given(cfg=fleet(), costed=st.booleans())
    def test_every_slot_reachable_by_steal_survey(self, cfg, costed):
        """Work parked on any slot's own list must be stealable from any
        other slot: the survey walks every covering level, so no shard of
        the fleet is invisible to an idle slot anywhere else."""
        pods, hosts, group, n_slots = cfg
        topo = slots_topology(n_slots, group, hosts=hosts, pods=pods)
        cm = SERVE_COST if costed else ZERO_COST
        # pin src/dst spot checks to the fleet corners + a mid slot: the
        # far corner pair crosses every level the topology has
        srcs = {0, n_slots - 1, n_slots // 2}
        for src in srcs:
            for dst in srcs:
                if src == dst:
                    continue
                pol = StealPolicy(topo, cost_model=cm)
                t = thread(4.0, name="loot", data="loot")
                pol.sched.queues.covering(dst)[0].push(t)
                got = pol.next(src, 0.0)
                assert got is t, (src, dst, costed)
                assert got.stolen                    # flagged for next-touch
                assert pol.sched.stats.steals == 1


# ---------------------------------------------------------------------------
# per-host execution determinism: sharding the decode changes nothing
# ---------------------------------------------------------------------------

class TestPerHostDecodeDeterminism:
    """The tentpole invariant: per-host decode batches + wave-batched
    prefill are *execution* changes only.  On any fleet shape, with mixed
    gangs / priorities / cross-host homes / mid-run regeneration, the
    sharded engine must decode bit-identical streams in the exact same
    number of engine steps as the global-batch engine."""

    def _drive(self, cfg, seed, per_host, wave):
        pods, hosts, group, n_slots = cfg
        eng = ServingEngine(None, None, n_slots=n_slots, group=group,
                            hosts=hosts, pods=pods,
                            backend=StubModelBackend(),
                            per_host_decode=per_host, wave_prefill=wave)
        rng = np.random.default_rng(seed)
        hostnames = [c.name for c in eng.topo.components("host")] \
            if pods * hosts > 1 else [None]
        gangs, n = [], 0
        for g in range(int(rng.integers(2, 5))):
            gang = f"g{g}" if rng.random() < 0.7 else None
            if gang is not None:
                gangs.append(gang)
            home = hostnames[int(rng.integers(0, len(hostnames)))]
            for _ in range(int(rng.integers(1, 6))):
                eng.submit(rng.integers(1, 200, 6), int(rng.integers(2, 8)),
                           prio=int(rng.integers(0, 3)), gang=gang,
                           home=home)
                n += 1
        steps = 0
        while not eng._drained() and steps < 4000:
            eng.step()
            steps += 1
            if gangs and steps % 5 == 0:
                eng.regenerate_gang(gangs[(steps // 5) % len(gangs)])
        assert len(eng.completed) == n, (cfg, len(eng.completed), n)
        return (eng.steps, {r.rid: tuple(r.out_tokens)
                            for r in eng.completed}, eng)

    @settings(max_examples=10, deadline=None)
    @given(cfg=fleet(), seed=st.integers(min_value=0, max_value=10 ** 6))
    @example(cfg=(2, 1, 1, 3), seed=0)   # lost a pending gang member
    def test_per_host_streams_equal_global_batch(self, cfg, seed):
        steps_g, streams_g, _ = self._drive(cfg, seed, False, False)
        steps_h, streams_h, eng = self._drive(cfg, seed, True, True)
        assert steps_h == steps_g
        assert streams_h == streams_g
        # the sharded engine really ran one batch per host
        n_hosts = cfg[0] * cfg[1]
        assert len(eng._exec_groups) == (n_hosts if n_hosts > 1 else 1)
        # every decoded token is accounted to exactly one host batch
        # (each request's FIRST token comes from prefill, not decode)
        assert sum(eng.stats.host_active_slots) == \
            sum(len(s) for s in streams_h.values()) - eng.stats.prefills

    def test_idle_host_skips_decode(self):
        """A host whose batch is empty launches no decode_step: its
        per-host ledger stays behind the busy host's."""
        eng = ServingEngine(None, None, n_slots=8, hosts=2,
                            backend=StubModelBackend())
        rng = np.random.default_rng(0)
        for _ in range(4):
            eng.submit(rng.integers(1, 200, 6), 6, home="host0")
        eng.run(max_steps=200)
        assert eng.stats.host_decode_steps[0] > 0
        assert eng.stats.host_decode_steps[1] == 0    # never woke up


# ---------------------------------------------------------------------------
# DCN-priced rebalancing: the host-local mode
# ---------------------------------------------------------------------------

class TestDCNRebalanceMode:
    def _run(self, local: bool):
        eng = ServingEngine(None, None, n_slots=32, pods=2, hosts=2,
                            backend=StubModelBackend(),
                            cost_model=SERVE_COST, dcn_rebalance=local)
        rng = np.random.default_rng(0)
        n = 0
        for _ in range(12):
            eng.submit(rng.integers(1, 250, 8), 24, gang="fat",
                       home="host0")
            n += 1
        for h in range(4):
            for g in range(2):
                for _ in range(8):
                    eng.submit(rng.integers(1, 250, 8), 4,
                               gang=f"h{h}g{g}", home=f"page{2 * h}")
                    n += 1
        eng.run(max_steps=8000)
        assert len(eng.completed) == n
        return eng

    def test_local_mode_buys_host_local_respreads(self):
        """On admission-bound within-host skew the priced trigger buys
        host-local re-spreads; the flat trigger never does (it has no
        host-local candidates at all) and its machine-wide deal pays
        level-table tolls — more stall for more steps.  Either way the
        decode streams are identical: rebalance mode is pure
        scheduling."""
        local = self._run(True)
        flat = self._run(False)
        assert local.stats.local_rebalances > 0
        assert flat.stats.local_rebalances == 0
        assert local.steps < flat.steps
        assert {r.rid: tuple(r.out_tokens) for r in local.completed} == \
            {r.rid: tuple(r.out_tokens) for r in flat.completed}

    def test_single_host_modes_identical(self):
        """No tabled boundary on a single host: both rebalance modes make
        bit-identical decisions and bills (the goldens depend on it)."""
        def run(local):
            eng = ServingEngine(None, None, n_slots=8,
                                backend=StubModelBackend(),
                                dcn_rebalance=local)
            rng = np.random.default_rng(1)
            for i in range(20):
                eng.submit(rng.integers(1, 200, 6), 8,
                           gang="fat" if i < 14 else None)
            eng.run(max_steps=2000)
            return (eng.steps, eng.stats.rebalances,
                    eng.sched.stats.rebalance_cost,
                    {r.rid: tuple(r.out_tokens) for r in eng.completed})

        assert run(True) == run(False)


# ---------------------------------------------------------------------------
# HBM accounting under random traffic
# ---------------------------------------------------------------------------

class TestHBMAccounting:
    def _check_ledger(self, eng):
        for page, used in enumerate(eng.hbm_used):
            assert -1e-9 <= used <= eng.hbm_budget + 1e-9, \
                (page, used, eng.hbm_budget)
        recomputed = [0.0] * len(eng.hbm_used)
        for slot, charged in enumerate(eng._slot_charged):
            if charged:
                recomputed[eng._page_of[slot]] += eng.kv_bytes
        assert recomputed == pytest.approx(eng.hbm_used)

    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10 ** 6),
           capacity_aware=st.booleans())
    @example(seed=124132, capacity_aware=True)   # lost a pending member
    def test_random_traffic_respects_budget_and_starves_nobody(
            self, seed, capacity_aware):
        rng = np.random.default_rng(seed)
        pods = int(rng.integers(1, 3))
        hosts = int(rng.integers(1, 3))
        n_hosts = pods * hosts
        n_slots = int(rng.integers(n_hosts, 4 * n_hosts + 1)) * 2
        budget = float(rng.integers(1, 4))
        eng = ServingEngine(None, None, n_slots=n_slots, group=4,
                            hosts=hosts, pods=pods,
                            backend=StubModelBackend(),
                            hbm_budget=budget, kv_bytes=1.0,
                            capacity_aware=capacity_aware)
        hostnames = [c.name for c in eng.topo.components("host")] \
            if n_hosts > 1 else [None]
        gangs, n = [], 0
        for g in range(int(rng.integers(2, 6))):
            gang = f"g{g}" if rng.random() < 0.8 else None
            if gang is not None:
                gangs.append(gang)
            home = hostnames[int(rng.integers(0, len(hostnames)))]
            for _ in range(int(rng.integers(1, 7))):
                eng.submit(rng.integers(1, 200, 6),
                           int(rng.integers(2, 9)),
                           prio=int(rng.integers(0, 3)), gang=gang,
                           home=home)
                n += 1
        steps = 0
        while not eng._drained() and steps < 6000:
            eng.step()
            steps += 1
            self._check_ledger(eng)
            if gangs and steps % 7 == 0:        # rolling backpressure
                eng.regenerate_gang(gangs[(steps // 7) % len(gangs)])
                self._check_ledger(eng)
        # refused loot was always re-admitted somewhere: every request
        # completed exactly once with exactly the asked-for tokens
        rids = sorted(r.rid for r in eng.completed)
        assert rids == list(range(n)), (n_slots, budget, len(rids), n)
        for r in eng.completed:
            assert len(r.out_tokens) == r.max_new_tokens
        assert all(u == 0.0 for u in eng.hbm_used)   # drained: all refunded

    def test_capacity_policy_never_changes_streams(self):
        """Aware and blind engines decode identical streams — capacity
        handling is pure scheduling."""
        def run(aware):
            eng = ServingEngine(None, None, n_slots=12, hosts=2,
                                backend=StubModelBackend(), hbm_budget=2.0,
                                capacity_aware=aware)
            rng = np.random.default_rng(3)
            for i in range(18):
                eng.submit(rng.integers(1, 200, 6), 8,
                           gang="fat" if i < 12 else None, home="host0")
            eng.run(max_steps=4000)
            return {r.rid: tuple(r.out_tokens) for r in eng.completed}

        assert run(True) == run(False)

    def test_full_group_refuses_steal_loot(self):
        """A page group at budget refuses in the survey: steal_refusals
        accounts it and no reservation ever exceeds the budget."""
        eng = ServingEngine(None, None, n_slots=8, hosts=2,
                            backend=StubModelBackend(), hbm_budget=1.0,
                            capacity_aware=True)
        rng = np.random.default_rng(0)
        for _ in range(12):
            eng.submit(rng.integers(1, 200, 6), 8, gang="fat", home="host0")
        eng.run(max_steps=2000)
        assert len(eng.completed) == 12
        assert eng.sched.stats.steal_refusals > 0
        assert eng.stats.hbm_slot_waits > 0         # parked, never bounced
        assert eng.stats.hbm_refusals == 0          # aware mode: no bounces


# ---------------------------------------------------------------------------
# rebalance-candidate scoping: keyed by component identity, not .index
# ---------------------------------------------------------------------------

class TestRebalanceCandidateScoping:
    @settings(max_examples=40)
    @given(cfg=fleet(), skew_host=st.integers(min_value=0, max_value=15))
    def test_skewed_host_is_candidate_by_identity(self, cfg, skew_host):
        """`_rebalance_candidates` must scope a re-spread to the exact
        host COMPONENT whose own page depths are skewed, on any 1-4 pod x
        ragged-host fleet.  The old lookup round-tripped the component
        through ``topo.components("host")[component.index]`` — an
        identity the Topology API never promises a consumer — so this
        pins the contract: the candidate *is* the skewed host object."""
        pods, hosts, group, n_slots = cfg
        eng = ServingEngine(None, None, n_slots=n_slots, group=group,
                            pods=pods, hosts=hosts,
                            backend=StubModelBackend())
        if eng._host_idx is None:
            return                      # single host: no host candidates
        host_comps = eng.topo.components("host")
        target = host_comps[skew_host % len(host_comps)]
        own_pages = [p for p, h in enumerate(eng._page_host)
                     if h is target]
        if len(own_pages) < 2:
            return                      # one-page host: skew undefined
        depths = [0] * len(eng._page_host)
        depths[own_pages[0]] = eng.depth_skew       # skew inside target only
        cands = eng._rebalance_candidates(depths)
        assert cands[-1] is None                     # machine-wide fallback
        assert len(cands) == 2
        assert cands[0] is target, \
            (cands[0].name if cands[0] else None, target.name)

    def test_all_skewed_hosts_enumerated(self):
        """Every host with internal skew appears, each by identity, in
        page order."""
        eng = ServingEngine(None, None, n_slots=24, group=3, pods=2,
                            hosts=3, backend=StubModelBackend())
        depths = [0] * len(eng._page_host)
        skewed = []
        seen = set()
        for p, h in enumerate(eng._page_host):
            if id(h) not in seen:
                seen.add(id(h))
                depths[p] = eng.depth_skew + 1
                skewed.append(h)
        cands = eng._rebalance_candidates(depths)
        assert cands[-1] is None
        assert all(a is b for a, b in zip(cands[:-1], skewed))
        assert len(cands) == len(skewed) + 1
