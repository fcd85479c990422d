"""Serving engine: continuous batching as the second SchedulerRuntime client.

Requests are *threads* (work = tokens still to decode, data = the gang's KV
page-group id); requests sharing a prompt prefix or an SLA class are grouped
into *bubbles*.  The engine owns a fixed-size decode batch and maps it onto
the scheduling model exactly as the paper prescribes for any workload:

=================  ==========================================================
scheduler concept  serving meaning
=================  ==========================================================
cpu (leaf)         decode batch slot
level              ``pod`` > ``host`` > ``page``: DCN shards, hosts within a
                   pod, and KV page groups (slots sharing a cache page) —
                   the full hierarchy when ``pods``/``hosts`` > 1, just
                   ``page`` on a single host
data object        a gang's KV state (``Thread.data`` = gang id)
steal              an idle slot pulls a queued gang from a loaded page
                   group — possibly across hosts, where the per-level cost
                   table prices the DCN crossing ~10x a page crossing
next touch         first post-migration admission re-homes the gang's KV via
                   a *batched* splice of parked per-request states — not the
                   old per-request re-prefill path
rebalance          queue-depth skew across page groups triggers one bulk
                   LPT re-spread (`BubbleScheduler.rebalance`), cost-gated
capacity           per-page-group HBM byte budgets: a full page group
                   refuses loot (the steal survey skips it, admission parks
                   the gang) instead of thrashing KV it cannot hold
=================  ==========================================================

The engine drives the same :class:`~repro.core.runtime.SchedulerRuntime`
loop as the discrete simulator — ``acquire`` (lookup + steal + cost
billing), ``touch`` (first/next-touch KV homing), ``rebalance_worth_it``
(the AdaptivePolicy-style cost-benefit trigger, fed by decode-gang queue
depths instead of steal-attempt windows).  ``mode="admission"`` keeps the
pre-runtime behaviour (no steal, no rebalance, first-touch homing) as the
measurable baseline for ``benchmarks/serve_gangs.py``.

Cost has a physical meaning here: a :class:`StealCostModel` penalty accrued
by a slot's scheduler call (remote page-group locks, KV drag) is billed as
*admission-latency steps* — the slot sits out that many engine steps before
its next decode, so steal-happy schedules pay for their migrations in the
engine's own currency.

**Execution follows the placement hierarchy** (the paper's core claim
applied to the execution substrate, not just the decisions): on a
multi-host fleet each host owns an independent decode batch — one
``decode_step`` call (one jit, one KV shard) per host per engine step —
and fresh same-length prompts admitted in one wave are prefilled in one
batched call per host (``prefill_wave``) instead of a per-request loop.
A host whose batch is empty skips its decode entirely, which is exactly
the per-shard latency a flat whole-fleet batch cannot model.  Slot
occupancy within a host batch is still a mask (empty slots decode padding
at negligible marginal cost on TPU).  Sharding the execution never
changes the decoded streams: slots are independent in every backend, so
per-host batches produce bit-identical tokens to the historical global
batch (property-tested across fleet topologies), and a single-host engine
*is* the historical global batch, byte for byte.

The model is behind a small backend interface so the scheduler stack can
be exercised hermetically: :class:`JaxModelBackend` runs the real zoo,
:class:`StubModelBackend` is a deterministic numpy stand-in (no jit
compile) for tests and CI benchmarks.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Optional

import numpy as np

from repro.core.bubble import Bubble, Thread, bubble, thread
from repro.core.policies import BubblePolicy, StealPolicy
from repro.core.runtime import SchedulerRuntime
from repro.core.scheduler import StealCostModel
from repro.core.topology import Level, Topology
from repro.core.trace import span

from .workload import goodput_under_sla, percentile

# The serving price list: a steal pays remote page-group lock traffic plus a
# per-level / per-request KV drag, a rebalance pays one bulk charge — all in
# engine steps (admission latency).  Small relative to typical decode
# lengths, so stealing stays profitable but not free; the queue-depth
# rebalance trigger needs the nonzero prices to pass its cost-benefit test.
#
# The ``level_table`` prices the multi-host boundaries: dragging KV across a
# ``host`` pays DCN round-trips (~10x the on-chip page shuffle once the
# extra tree distance is counted in) and across a ``pod`` pays the
# data-center network on top.  Single-host topologies have neither level,
# so every pre-existing single-host schedule is priced — and therefore
# traced — identically.
SERVE_COST = StealCostModel(lock_penalty=0.5, level_penalty=0.25,
                            thread_penalty=0.125, rebalance_base=1.0,
                            rebalance_per_move=0.125,
                            level_table=(("host", 3.0), ("pod", 6.0)))

# What a DCN-naive scheduler believes: the same prices with the per-level
# table dropped — a cross-host steal looks barely dearer than a cross-page
# one.  Derived from SERVE_COST so the two can only ever differ in the
# table (the multihost benchmark's validity depends on exactly that).
# Pair it with ``bill_model=SERVE_COST`` and the engine keeps choosing
# remote loot it must then pay real DCN latency for: the measurable
# baseline for ``serve/multihost_steal_speedup``.
FLAT_SERVE_COST = dataclasses.replace(SERVE_COST, level_table=())

# The *bandwidth-priced* machine: the same boundary bases, plus a per-byte
# term — a transfer's bill scales with the KV bytes it drags (``kv_bytes``
# x live threads, the engine's own HBM-ledger ruler wired into the
# scheduler as ``bytes_cb``).  Dragging a fat gang across a ``host``
# boundary now costs proportionally more than a singleton at the same
# distance, which is what the DCN actually charges.  The rates are
# asymmetric on purpose: within-pod (``host``) moves ride the fast
# interconnect (cheap per byte), cross-``pod`` moves ride the DCN — so a
# byte-aware survey keeps heavy KV inside the pod while a byte-naive one
# sees only the flat bases, whose cross/same ratio the per-byte term
# roughly doubles.  A bandwidth-naive scheduler believes ``SERVE_COST``
# (flat boundary tolls) while paying ``BW_SERVE_COST`` (``bill_model``):
# the measurable baseline for ``serve/bandwidth_priced_speedup``.  With
# every ``per_byte`` zero the triple form prices bit-identically to the
# pair form, so SERVE_COST itself — and every golden trace — is untouched.
BW_SERVE_COST = dataclasses.replace(
    SERVE_COST, level_table=(("host", 3.0, 0.25), ("pod", 6.0, 2.0)))

# Levels a ``slots_topology`` fleet deliberately does NOT price in the
# level table: crossings below ``host`` (and the degenerate ``batch`` /
# ``pod`` roots) fall back to the flat ``level_penalty`` per level
# crossed — on-chip shuffles are latency, not DCN bandwidth.  The cost-
# model coverage test pins every topology level to either this set or a
# ``level_table`` entry, so a new level cannot silently price at zero.
SERVE_FREE_LEVELS = frozenset({"batch", "page", "slot"})


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int
    prio: int = 0
    gang: Optional[str] = None         # co-schedule group (shared prefix)
    out_tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    # -- SLA / latency ledger (open-loop traffic) --
    # ``sla`` is the submitted CONTRACT class — immutable, it is what the
    # request's TTFT/goodput are judged by.  ``tier`` is the SCHEDULING
    # class — starts equal to ``sla`` and sinks under the multilevel-
    # feedback demotion rule (a long-runner stops competing as
    # interactive, but is still *measured* as one).
    sla: Optional[str] = None
    tier: Optional[str] = None
    submit_step: int = 0               # engine step the request was queued
    first_token_step: Optional[int] = None   # step the prefill token landed
    last_token_step: Optional[int] = None    # step of the latest token
    finish_step: Optional[int] = None        # step the request completed
    # -- agentic sessions (tool calls / multi-turn) --
    # ``tool_calls`` is a tuple of ``(at_tokens, think_steps)`` markers:
    # when the request's emitted-token count reaches ``at_tokens`` it
    # blocks on an external event (a tool response) for ``think_steps``
    # engine steps (``None`` = until the client calls ``engine.wake``).
    # No tokens are injected on wake, so a request's stream is a pure
    # function of its prompt — identical with or without the sleeps.
    tool_calls: tuple = ()
    next_call: int = 0                 # index of the next unfired marker
    wake_step: Optional[int] = None    # step the latest tool response landed
    # a resumed service interval: the first token after a park/sleep is
    # not an inter-token gap (the request was not being served) — the
    # latency ledger records wake-to-token instead when ``wake_step`` is
    # set, and nothing otherwise
    service_break: bool = False


@dataclasses.dataclass
class EngineStats:
    """Engine-side ledger (scheduler counters live in ``sched.stats``).

    Counting conventions worth pinning down (previously folklore):

    * ``prefills`` counts **requests** prefilled (each fresh prompt once,
      however they are batched); ``prefill_waves`` counts the **backend
      calls** that ran them — with wave batching on, ``prefill_waves <=
      prefills`` and the gap is the batching win.
    * ``kv_splices`` counts batched splice **ops** (one per host batch per
      admission wave), ``kv_spliced_slots`` the slots they wrote.
    * ``hbm_slot_waits`` vs ``hbm_refusals`` — the two HBM events are
      distinct and mode-exclusive: a *wait* is a capacity-**aware** slot
      sitting out an admission wave because its page group is at budget
      (one count per slot per step with work queued — a backpressure
      gauge, no work wasted); a *refusal* is a capacity-**blind** claim
      bounced at splice time, after the scheduler call and any steal bill
      already ran — pure wasted work.  Comparing the two across modes is
      how ``serve/hbm_pressure_refusal_speedup`` reads.
    * ``host_decode_steps[h]`` / ``host_active_slots[h]`` — the per-host
      execution ledger: decode calls host ``h`` actually ran (it skips
      steps where its batch is empty) and the cumulative occupied-slot
      count over those calls.  Host skew that placement hides shows up
      here: a flooded host runs every step near-full while its neighbours
      idle.  Single-host engines have one entry (the whole batch).
    * ``host_skipped_steps[h]`` — straggler stalls: engine steps host
      ``h`` had occupied slots but its speed credit had not reached a
      whole decode yet (``host_speed[h] < 1``), so its batch sat still.
      Always zero at nominal speed.  Effective per-host throughput is
      ``host_active_slots[h] / engine steps`` (the ``host_throughput``
      counter): a 0.5x host with full slots decodes half the tokens per
      engine step a nominal host would.
    * ``gang_splits`` / ``gang_split_members`` — HBM-aware gang
      splitting: whole-gang admissions the HBM ledger refused that were
      cheaper to split across sibling page groups (the bubble expanded
      one level, overflow members re-homed) than to park until the home
      group drained; ``gang_split_members`` counts the members actually
      moved to siblings.
    * ``host_kills`` / ``host_joins`` / ``orphaned`` / ``kv_restores`` /
      ``reprefills`` — the elastic-fleet ledger: hosts removed/added
      live, resident requests whose KV died with a host, and how each
      orphan was brought back (snapshot restore + replay vs re-prefill
      from scratch — whichever the cost model quoted cheaper).
    * the agentic ledger: ``sleeps`` counts tool-call slot releases
      (sleep-and-release mode), ``holds`` tool calls that kept their slot
      (the baseline) and ``hold_slot_steps`` the slot-steps those held
      slots sat idle; ``wakes`` counts tool responses delivered, split
      ``wake_home`` (spliced back under the session's old page group) vs
      ``wake_away`` (the wake-affinity quote found somewhere cheaper and
      billed the move); ``stale_evictions`` sessions whose parked KV was
      dropped past ``session_ttl`` and ``wake_reprefills`` the wakes that
      consequently had to rebuild their continuation from the full
      history.
    """

    prefills: int = 0            # fresh REQUESTS prefilled (not calls)
    prefill_waves: int = 0       # batched prefill CALLS issued
    kv_splices: int = 0          # batched splice ops issued
    kv_spliced_slots: int = 0    # slots written by those splices
    kv_parks: int = 0            # per-request KV states parked
    kv_migrations: int = 0       # next-touch re-homes of a gang's KV
    kv_page_moves: int = 0       # ...of which crossed page groups
    kv_host_moves: int = 0       # ...of which crossed hosts (DCN traffic)
    rebalances: int = 0          # queue-depth-triggered re-spreads
    local_rebalances: int = 0    # ...of which host-scoped (DCN-free)
    stall_steps: float = 0.0     # admission latency billed by the cost model
    preemptions: int = 0         # SLA preemption firings (one victim each)
    preempt_parks: int = 0       # requests parked by those firings
    demotions: int = 0           # multilevel-feedback tier demotions
    hbm_slot_waits: int = 0      # aware: full-group slots skipping waves
    hbm_refusals: int = 0        # blind: claims bounced at splice time
    gang_splits: int = 0         # gangs split across sibling page groups
    gang_split_members: int = 0  # members re-homed by those splits
    # elastic-fleet ledger (kill_host / join_host)
    host_kills: int = 0          # hosts removed live
    host_joins: int = 0          # hosts added live
    orphaned: int = 0            # residents whose KV died with a host
    kv_restores: int = 0         # orphans resumed from the KV snapshot store
    reprefills: int = 0          # orphans recomputed from scratch
    # agentic ledger (tool-call sleep/wake)
    sleeps: int = 0              # tool calls that released their slot
    holds: int = 0               # tool calls that kept it (baseline)
    hold_slot_steps: int = 0     # slot-steps held slots sat idle thinking
    wakes: int = 0               # tool responses delivered
    wake_home: int = 0           # ...spliced back under the home page group
    wake_away: int = 0           # ...re-homed by the wake-affinity quote
    wake_reprefills: int = 0     # wakes that rebuilt KV from history
    stale_evictions: int = 0     # sleeping sessions whose KV hit session_ttl
    # per-host execution ledger (sized by the engine at construction)
    host_decode_steps: list = dataclasses.field(default_factory=list)
    host_active_slots: list = dataclasses.field(default_factory=list)
    host_skipped_steps: list = dataclasses.field(default_factory=list)


def _fanout(sizes: list[int]):
    """Collapse a uniform per-parent fanout list to its int form (keeps
    ``Topology.describe()`` and the goldens' layouts identical for the
    historical uniform cases)."""
    return sizes[0] if len(set(sizes)) == 1 else sizes


def slots_topology(n_slots: int, group: int = 4, *, hosts: int = 1,
                   pods: int = 1, page_factor: float = 2.0,
                   host_factor: float = 4.0,
                   dcn_factor: float = 8.0) -> Topology:
    """Model the decode fleet as a hierarchy: pods shard the fleet across
    the DCN, hosts within a pod each own a decode batch, slot groups share
    a KV page (affinity level), slots are the leaves.

    ``n_slots`` is the total slot count and need not divide evenly at any
    level: slots are dealt across the ``pods * hosts`` hosts (sizes differ
    by at most one), each host's slots are split into KV page groups of at
    most ``group``, and **every** slot is a schedulable leaf (the old
    ``n_slots // group`` derivation silently dropped the remainder —
    ``n_slots=9, group=4`` built 2x4 leaves and slot 8 could never be
    admitted to).  Ragged splits everywhere ride on the per-parent fanout
    lists :class:`~repro.core.topology.Level` grew for exactly this.

    Level layout: ``batch > [pod >] [host >] page > slot`` — the ``pod``
    level appears only when ``pods > 1`` and the ``host`` level whenever
    the fleet has more than one host, so the historical single-host
    topology (and every golden trace over it) is byte-identical.
    """
    assert n_slots >= 1, n_slots
    assert hosts >= 1 and pods >= 1, (hosts, pods)
    n_hosts = hosts * pods
    assert n_slots >= n_hosts, \
        f"need >=1 slot per host ({n_slots} slots, {n_hosts} hosts)"
    base, rem = divmod(n_slots, n_hosts)
    host_slots = [base + 1] * rem + [base] * (n_hosts - rem)
    page_counts: list[int] = []           # pages per host, host order
    slot_sizes: list[int] = []            # slots per page, page order
    for hs in host_slots:
        groups = max(-(-hs // group), 1)             # ceil division
        b, r = divmod(hs, groups)
        page_counts.append(groups)
        slot_sizes += [b + 1] * r + [b] * (groups - r)
    levels = [Level("batch", 1)]
    if pods > 1:
        levels.append(Level("pod", pods, factor=dcn_factor))
    if n_hosts > 1:
        levels.append(Level("host", hosts if pods > 1 else n_hosts,
                            factor=host_factor))
    levels += [Level("page", _fanout(page_counts), factor=page_factor),
               Level("slot", _fanout(slot_sizes))]
    return Topology(levels)


# ---------------------------------------------------------------------------
# model backends
# ---------------------------------------------------------------------------

class JaxModelBackend:
    """The real model zoo: jitted whole-batch decode + per-request prefill.

    Which axis of each state leaf carries the batch is *inferred*, not
    guessed: ``api.batch_axis_spec`` pins it per leaf by comparing state
    shapes at two batch sizes (``-1`` marks batch-free leaves, passed
    through untouched).  The old ``ndim >= 2`` heuristic assumed "axis 1
    if the leaf has one" — true for every reps-stacked cache today, but it
    silently skipped genuine 1-D per-slot leaves, and a skipped leaf means
    a spliced request resumes with another request's state."""

    def __init__(self, cfg, params, cache_len: int):
        import jax  # deferred: stub-mode users never pay the import
        from repro.models import api
        self._jax = jax
        self._api = api
        self.cfg = cfg
        self.params = params
        self.cache_len = cache_len
        self._decode = jax.jit(api.make_decode_fn(cfg))
        self._prefill = jax.jit(api.make_prefill_fn(cfg, cache_len))
        self._axes = api.batch_axis_spec(
            lambda n: api.lm.init_state(cfg, n, cache_len))

    def init(self, n_slots: int) -> tuple:
        states = self._api.lm.init_state(self.cfg, n_slots, self.cache_len)
        return states, np.zeros((n_slots, 1), np.int32)

    def _slice(self, states, i: int):
        """One sequence's state: index the batch axis of every batch leaf
        (keepdims, so slices concatenate back in a splice)."""
        lax = self._jax.lax
        return self._jax.tree.map(
            lambda ax, b: b if ax < 0
            else lax.index_in_dim(b, i, ax, keepdims=True),
            self._axes, states)

    def prefill(self, prompt: np.ndarray) -> tuple[int, object]:
        jnp = self._jax.numpy
        logits, st = self._prefill(self.params, {"tokens":
                                                 jnp.asarray(prompt[None, :])})
        tok = int(jnp.argmax(logits, axis=-1).astype(jnp.int32)[0])
        return tok, st

    def prefill_wave(self, prompts: list) -> list:
        """Prefill a wave of same-length prompts in ONE model call.

        ``lm.prefill`` is natively batched ((B, S) tokens → (B, V) last
        logits + batched states), so the wave costs one forward pass; the
        batched state is split back into per-sequence slices so the
        admission splice can route each to its slot.  Returns
        ``[(first_token, state), ...]`` in prompt order — identical values
        to ``prefill`` run per request."""
        jnp = self._jax.numpy
        logits, st = self._prefill(self.params,
                                   {"tokens": jnp.asarray(np.stack(prompts))})
        toks = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        return [(int(toks[i]), self._slice(st, i))
                for i in range(len(prompts))]

    def decode(self, tokens: np.ndarray, states) -> tuple[np.ndarray, object]:
        jnp = self._jax.numpy
        logits, states = self._decode(self.params, jnp.asarray(tokens), states)
        next_tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)  # (B,)
        return next_tok, states

    def splice(self, states, pairs: list[tuple[int, object]]):
        """Write several single-sequence states into their batch slots in
        ONE traversal — the batched next-touch splice (the old engine
        spliced once per request)."""
        jnp = self._jax.numpy
        slots = jnp.asarray([s for s, _ in pairs])

        def write(ax, b, *ones):
            if ax < 0:
                return b
            idx = (slice(None),) * ax + (slots,)
            return b.at[idx].set(jnp.concatenate(ones, axis=ax))

        return self._jax.tree.map(write, self._axes, states,
                                  *[st for _, st in pairs])

    def extract(self, states, slot: int):
        return self._slice(states, slot)

    def peek(self, states, slot: int):
        """Non-mutating read of one slot's state — what the KV snapshot
        store writes on its cadence.  Identical to :meth:`extract` here
        (slicing copies); a distinct name because the *paged* backend's
        extract is a destructive table edit and must never be used for
        snapshots — the engine requires ``peek`` to enable a ``kv_store``."""
        return self._slice(states, slot)


class _PagedShard:
    """One execution group's paged KV: device-side pools (inside
    ``states``) plus the host-side page metadata the backend edits —
    the block table, per-slot lengths, the free list, and per-slot page
    ownership.  The engine holds this object opaquely as the group's
    "states"."""

    __slots__ = ("states", "table", "lengths", "free", "slot_pages")

    def __init__(self, states, table, lengths, free, slot_pages):
        self.states = states          # list[stage] of tuple[pos] pytrees
        self.table = table            # (n_slots, pages_per_slot) np.int32
        self.lengths = lengths        # (n_slots,) np.int32
        self.free = free              # allocatable pool page ids (0 = trash)
        self.slot_pages = slot_pages  # slot -> [page ids], allocation order


class PagedJaxModelBackend:
    """The model zoo on paged KV: a steal/park/splice is a block-table
    edit, not a tensor copy.

    The KV layout mirrors the engine's page groups: every attention layer
    reads K/V from a shared page pool through one per-shard block table
    (``models.paged``), so the state that used to *move* with a request —
    per-layer ``(B, C, K, hd)`` cache rows — is pinned, and only metadata
    moves:

    * ``extract`` (park, steal-time KV drag) hands back the slot's page
      ids + recurrent-state slices and zeroes its table row — no pool
      read;
    * ``splice`` of a parked handle into the same shard re-points the new
      slot's table row at those pages — no pool write (counted in
      ``stats["table_splices"]``); only a *cross-shard* splice (a DCN
      move between host batches) copies pages between pools
      (``stats["pool_copies"]``, in pages);
    * fresh prefills are the one real pool write: the prompt's K/V pages
      are scattered in, batched per layer per admission wave
      (``stats["pool_page_writes"]``).

    Decode stays one jit per host batch with a stable signature
    ``(params, tokens, states, table, lengths)``.  Pages are allocated
    lazily as a slot's length crosses page boundaries; page 0 is the
    trash page free slots decode into.  Recurrent leaves (rwkv6/rglru —
    fixed-size O(1) states) ride the same explicit batch-axis spec as the
    dense backend: they are spliced by value, which for an O(1) state *is*
    the cheap move.

    Streams are identical to :class:`JaxModelBackend` by construction
    when ``cache_len`` has no sliding-window ring (see
    ``kernels.ref.paged_sdpa_ref``); the serving benchmark and the engine
    property tests assert it token-for-token.
    """

    def __init__(self, cfg, params, cache_len: int, *, page_size: int = 16,
                 use_kernel: bool = False, slack_slots: Optional[int] = None,
                 hbm_bytes: Optional[int] = None):
        import jax
        from repro.models import api, lm, paged
        assert not cfg.enc_layers, "paged serving: decoder-only models"
        assert cache_len % page_size == 0, (cache_len, page_size)
        self._jax = jax
        self._api = api
        self._lm = lm
        self._paged = paged
        self.cfg = cfg
        self.params = params
        self.cache_len = cache_len
        self.page_size = page_size
        self.pages_per_slot = cache_len // page_size
        # parked requests keep their pages resident while their old slot
        # re-admits someone else, so the pool carries slack beyond
        # n_slots * pages_per_slot; ``slack_slots`` sizes it (default: one
        # extra fleet's worth — parked work is bounded by live requests)
        self.slack_slots = slack_slots
        # ``hbm_bytes`` replaces the slack heuristic with the ledger: the
        # pool holds exactly what the per-shard HBM byte budget buys
        # (capacity == hbm_bytes // page bytes; the trash page rides on
        # top — it is pool bookkeeping, not budgeted KV).  Parked pages
        # stay resident in the pool, so on a budget-sized pool parked KV
        # competes for the same real bytes the admission ledger governs —
        # physical, unlike slack sizing, which quietly granted parked
        # requests a second fleet's worth of HBM.
        self.hbm_bytes = hbm_bytes
        self.page_bytes = paged.kv_page_bytes(cfg, page_size)
        self.use_kernel = use_kernel
        self._decode = jax.jit(api.make_paged_decode_fn(cfg, use_kernel))
        self._prefill = jax.jit(api.make_prefill_fn(cfg, cache_len))
        self._dense_axes = api.batch_axis_spec(
            lambda n: lm.init_state(cfg, n, cache_len))
        self._paged_axes = api.batch_axis_spec(
            lambda n: paged.init_paged_state(cfg, n, 4, page_size))
        self.stats = {"pool_page_writes": 0, "pool_copies": 0,
                      "table_splices": 0}

    # -- pool bookkeeping (host-side metadata) --------------------------------
    def init(self, n_slots: int) -> tuple:
        if self.hbm_bytes is not None and self.page_bytes > 0:
            # ledger-sized pool: capacity is what the byte budget buys
            num_pages = 1 + int(self.hbm_bytes) // self.page_bytes
            assert num_pages > 1, \
                f"hbm_bytes={self.hbm_bytes} buys no page " \
                f"(page_bytes={self.page_bytes})"
        else:
            slack = n_slots if self.slack_slots is None else self.slack_slots
            num_pages = 1 + (n_slots + slack) * self.pages_per_slot
        shard = _PagedShard(
            states=self._paged.init_paged_state(
                self.cfg, n_slots, num_pages, self.page_size),
            table=np.zeros((n_slots, self.pages_per_slot), np.int32),
            lengths=np.zeros((n_slots,), np.int32),
            free=list(range(1, num_pages)),
            slot_pages=[[] for _ in range(n_slots)])
        return shard, np.zeros((n_slots, 1), np.int32)

    def _alloc(self, shard: _PagedShard, n: int) -> list[int]:
        if len(shard.free) < n:
            raise RuntimeError(
                f"KV page pool exhausted ({n} pages requested, "
                f"{len(shard.free)} free): raise slack_slots or cache_len")
        pages, shard.free = shard.free[:n], shard.free[n:]
        return pages

    def _ensure_pages(self, shard: _PagedShard) -> None:
        """Lazy page allocation: before a decode call, any occupied slot
        whose next write position crosses into an unmapped page gets one
        from the free list — the vLLM-style on-demand grow that keeps a
        short request from reserving its worst-case KV upfront."""
        for b, pages in enumerate(shard.slot_pages):
            if not pages:
                continue                      # free slot: decodes into trash
            pi = int(shard.lengths[b]) // self.page_size
            if pi >= self.pages_per_slot:
                raise RuntimeError(
                    f"slot {b} reached cache_len={self.cache_len}: the "
                    f"engine admitted prompt+decode longer than the cache")
            if shard.table[b, pi] == 0:
                (pg,) = self._alloc(shard, 1)
                shard.table[b, pi] = pg
                pages.append(pg)

    # -- handles --------------------------------------------------------------
    def _fresh_handle(self, dense_states, i: int, length: int) -> dict:
        """One prefilled sequence, sliced out of a (possibly batched)
        dense prefill: attention K/V kept dense per layer (paged in at
        splice), every other state leaf sliced on its batch axis."""
        lax = self._jax.lax
        kv, leaves = {}, {}
        for si, (pat, _) in enumerate(self._lm._stages(self.cfg)):
            for pi, kind in enumerate(pat):
                st = dense_states[si][pi]
                if kind == "attn":
                    # KVCache k/v are (reps, B, C, K, hd); the prompt's
                    # tokens sit at positions [0, length) — ring-free as
                    # long as length <= C, asserted at prefill
                    kv[(si, pi)] = (st.k[:, i, :length], st.v[:, i, :length])
                else:
                    leaves[(si, pi)] = self._jax.tree.map(
                        lambda ax, b: b if ax < 0
                        else lax.index_in_dim(b, i, ax, keepdims=True),
                        self._dense_axes[si][pi], st)
        return {"kind": "fresh", "length": length, "kv": kv,
                "leaves": leaves}

    def prefill(self, prompt: np.ndarray) -> tuple[int, object]:
        return self.prefill_wave([prompt])[0]

    def prefill_wave(self, prompts: list) -> list:
        jnp = self._jax.numpy
        S = len(prompts[0])
        C = self._lm._cache_len(self.cfg, self.cache_len)
        assert S <= C, \
            f"paged prefill keeps the whole prompt resident ({S} > {C})"
        with span("prefill.forward"):
            logits, st = self._prefill(
                self.params, {"tokens": jnp.asarray(np.stack(prompts))})
        with span("prefill.readback"):
            toks = np.asarray(jnp.argmax(logits, axis=-1).astype(jnp.int32))
        with span("prefill.handles"):
            return [(int(toks[i]), self._fresh_handle(st, i, S))
                    for i in range(len(prompts))]

    # -- decode ---------------------------------------------------------------
    def decode(self, tokens: np.ndarray, shard: _PagedShard
               ) -> tuple[np.ndarray, object]:
        jnp = self._jax.numpy
        with span("decode.prep"):
            self._ensure_pages(shard)
            tokens = jnp.asarray(tokens)
            table = jnp.asarray(shard.table)
            lengths = jnp.asarray(shard.lengths)
        with span("decode.launch"):
            logits, shard.states = self._decode(
                self.params, tokens, shard.states, table, lengths)
        # every slot's position advances, occupied or not — the host-side
        # mirror of the dense path's ``pos + 1`` for the whole batch
        shard.lengths = shard.lengths + 1
        with span("decode.readback"):
            next_tok = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        return next_tok, shard

    # -- splice / extract: migration as metadata ------------------------------
    def splice(self, shard: _PagedShard, pairs: list[tuple[int, object]]):
        jnp = self._jax.numpy
        pool_pages: dict[tuple, list] = {}    # (si,pi) -> [(pages, k, v)]
        leaf_writes: dict[tuple, list] = {}   # (si,pi) -> [(slot, tree)]
        ps = self.page_size
        for slot, h in pairs:
            assert not shard.slot_pages[slot], \
                f"splice into slot {slot} which still owns pages"
            if h["kind"] == "fresh":
                pages: list[int] = []
                if h["kv"]:  # attention-free models own no pages
                    npg = -(-h["length"] // ps)
                    pages = self._alloc(shard, npg)
                    for (si, pi), (k, v) in h["kv"].items():
                        pad = [(0, 0), (0, npg * ps - h["length"]),
                               (0, 0), (0, 0)]
                        kp = jnp.pad(k, pad).reshape(
                            k.shape[0], npg, ps, *k.shape[2:])
                        vp = jnp.pad(v, pad).reshape(
                            v.shape[0], npg, ps, *v.shape[2:])
                        pool_pages.setdefault((si, pi), []).append(
                            (pages, kp, vp))
                    self.stats["pool_page_writes"] += npg
            else:                              # parked paged handle
                src: _PagedShard = h.pop("shard")
                pages = h.pop("pages")
                if src is shard or not pages:
                    # same pool: the migration IS the metadata write
                    self.stats["table_splices"] += 1
                else:
                    # cross-shard (a DCN move between host batches): the
                    # one place pages physically move — copy them between
                    # pools, then free the source's
                    dst = self._alloc(shard, len(pages))
                    src_idx = jnp.asarray(pages)
                    dst_idx = jnp.asarray(dst)
                    for si, (pat, _) in enumerate(
                            self._lm._stages(self.cfg)):
                        new_stage = list(shard.states[si])
                        for pi, kind in enumerate(pat):
                            if kind != "attn":
                                continue
                            pool = shard.states[si][pi]
                            spool = src.states[si][pi]
                            new_stage[pi] = self._paged.PagedKV(
                                k=pool.k.at[:, dst_idx].set(
                                    spool.k[:, src_idx]),
                                v=pool.v.at[:, dst_idx].set(
                                    spool.v[:, src_idx]))
                        shard.states[si] = tuple(new_stage)
                    src.free.extend(pages)
                    self.stats["pool_copies"] += len(pages)
                    pages = dst
            shard.slot_pages[slot] = list(pages)
            shard.table[slot, :] = 0
            shard.table[slot, :len(pages)] = pages
            shard.lengths[slot] = h["length"]
            for key, tree in h["leaves"].items():
                leaf_writes.setdefault(key, []).append((slot, tree))
        with span("splice.page_in") as sp:
            if sp is not None:
                sp["pages"] = sum(len(pages) for pages, _, _ in
                                  next(iter(pool_pages.values()), ()))
            # apply the queued fresh-prefill page-ins: ONE scatter per layer
            for (si, pi), entries in pool_pages.items():
                pool = shard.states[si][pi]
                idx = jnp.asarray([p for pages, _, _ in entries
                                   for p in pages])
                kcat = jnp.concatenate([k for _, k, _ in entries], axis=1)
                vcat = jnp.concatenate([v for _, _, v in entries], axis=1)
                new_stage = list(shard.states[si])
                new_stage[pi] = self._paged.PagedKV(
                    k=pool.k.at[:, idx].set(kcat.astype(pool.k.dtype)),
                    v=pool.v.at[:, idx].set(vcat.astype(pool.v.dtype)))
                shard.states[si] = tuple(new_stage)
            # batch-axis leaves (recurrent states): one traversal per layer
            for (si, pi), entries in leaf_writes.items():
                slots = jnp.asarray([s for s, _ in entries])

                def write(ax, b, *ones):
                    if ax < 0:
                        return b
                    idx = (slice(None),) * ax + (slots,)
                    return b.at[idx].set(jnp.concatenate(ones, axis=ax))

                new_stage = list(shard.states[si])
                new_stage[pi] = self._jax.tree.map(
                    write, self._paged_axes[si][pi], shard.states[si][pi],
                    *[t for _, t in entries])
                shard.states[si] = tuple(new_stage)
        return shard

    def extract(self, shard: _PagedShard, slot: int):
        """Park one slot: hand its pages to the caller (ownership moves
        with the handle — ``release`` is NOT called on parked pages) and
        zero its table row, so the freed slot's ongoing trash decode
        cannot touch the parked KV."""
        lax = self._jax.lax
        leaves = {}
        for si, (pat, _) in enumerate(self._lm._stages(self.cfg)):
            for pi, kind in enumerate(pat):
                if kind == "attn":
                    continue
                leaves[(si, pi)] = self._jax.tree.map(
                    lambda ax, b: b if ax < 0
                    else lax.index_in_dim(b, slot, ax, keepdims=True),
                    self._paged_axes[si][pi], shard.states[si][pi])
        handle = {"kind": "paged", "shard": shard,
                  "pages": shard.slot_pages[slot],
                  "length": int(shard.lengths[slot]), "leaves": leaves}
        shard.slot_pages[slot] = []
        shard.table[slot, :] = 0
        shard.lengths[slot] = 0
        return handle

    def release(self, shard: _PagedShard, slot: int):
        """Free a finished slot's pages back to the pool (the engine's
        ``_evict`` hook).  Parked slots were already emptied by
        ``extract`` — this is then a no-op."""
        shard.free.extend(shard.slot_pages[slot])
        shard.slot_pages[slot] = []
        shard.table[slot, :] = 0
        shard.lengths[slot] = 0
        return shard

    def drop(self, handle) -> None:
        """Free a *parked* handle's pages back to their source pool
        without ever splicing it in — stale-session eviction: the engine
        lets go of a sleeping session's KV to reclaim the pages, and a
        later wake rebuilds the continuation by re-prefill.  Fresh
        (never-paged) handles own no pool pages and are a no-op."""
        if not isinstance(handle, dict) or handle.get("kind") != "paged":
            return
        src = handle.get("shard")
        pages = handle.get("pages") or []
        if src is not None and pages:
            src.free.extend(pages)
        handle["pages"] = []


class StubModelBackend:
    """Deterministic numpy decode/prefill stand-in — no jax, no jit.

    Each slot's "KV state" is ``(position, history_hash)``; the next token
    is a function of the full token history, so any KV mishandling (a lost
    splice, a stale slot, a wrong-slot write) changes the output stream and
    is caught by equality tests.  This is what tests and the CI serving
    benchmark run: the scheduler stack is identical, only the model is
    stubbed."""

    M = 2_147_483_647                 # hash modulus (prime, fits int64)

    def __init__(self, vocab: int = 251):
        self.vocab = vocab

    def init(self, n_slots: int) -> tuple[np.ndarray, np.ndarray]:
        return (np.zeros((n_slots, 2), np.int64),
                np.zeros((n_slots, 1), np.int32))

    def _fold(self, acc: int, tok: int) -> int:
        return (acc * 31 + int(tok) + 1) % self.M

    def prefill(self, prompt: np.ndarray) -> tuple[int, np.ndarray]:
        acc = 0
        for tok in np.asarray(prompt).ravel():
            acc = self._fold(acc, tok)
        return acc % self.vocab, np.array([len(prompt), acc], np.int64)

    def prefill_wave(self, prompts: list) -> list:
        """Vectorised same-length prefill: fold all rows column by column.

        Exact-equal to per-request :meth:`prefill` (the fold stays inside
        int64: acc < 2^31, so acc*31 + tok fits with room to spare) —
        wave batching must never change a stream."""
        arr = np.asarray(np.stack(prompts), np.int64)          # (B, S)
        acc = np.zeros(len(arr), np.int64)
        for j in range(arr.shape[1]):
            acc = (acc * 31 + arr[:, j] + 1) % self.M
        return [(int(a % self.vocab), np.array([arr.shape[1], a], np.int64))
                for a in acc]

    def decode(self, tokens: np.ndarray, states: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
        acc = (states[:, 1] * 31 + tokens[:, 0].astype(np.int64) + 1) % self.M
        out = np.stack([states[:, 0] + 1, acc], axis=1)
        return (acc % self.vocab).astype(np.int32), out

    def splice(self, states: np.ndarray, pairs: list[tuple[int, np.ndarray]]
               ) -> np.ndarray:
        states = states.copy()
        for slot, row in pairs:
            states[slot] = row
        return states

    def extract(self, states: np.ndarray, slot: int) -> np.ndarray:
        return states[slot].copy()

    def peek(self, states: np.ndarray, slot: int) -> np.ndarray:
        """Non-mutating snapshot read (same as extract for this backend)."""
        return states[slot].copy()

    def replay(self, state: np.ndarray, tokens) -> np.ndarray:
        """Teacher-forced advance of one saved state through known output
        tokens — the checkpoint-restore fast path: a snapshot taken after
        m' emitted tokens plus a replay of tokens m'..m-1 reproduces the
        live state after m tokens exactly (decode is the same fold)."""
        pos, acc = int(state[0]), int(state[1])
        for tok in np.asarray(tokens, np.int64).ravel():
            acc = self._fold(acc, tok)
            pos += 1
        return np.array([pos, acc], np.int64)


# ---------------------------------------------------------------------------
# agentic sessions: the sleeping ledger
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SleepEntry:
    """One session blocked on an external event (a tool response).

    In sleep-and-release mode the entry lives in the engine's
    :class:`SleepingLedger` and the thread is held off every run queue;
    in the hold-the-slot baseline the same record sits in
    ``ServingEngine._thinking`` keyed by the slot it refuses to give up.
    Either way ``state`` is the parked backend KV handle (``None`` once a
    stale eviction dropped it), ``token`` the last emitted token the
    resumed decode feeds on, and ``home_page`` the page-group component
    the session slept under — the anchor of the wake-affinity quote."""

    rid: int
    thread: Thread
    state: object
    token: int
    home_page: object
    slept_step: int
    wake_at: Optional[int]            # None: waits for engine.wake(rid)
    retained: Optional[int] = None    # page-group index still holding the
                                      # session's HBM reservation
                                      # (``sleep_retain_hbm``)


class SleepingLedger:
    """rid-keyed registry of sessions asleep on external events.

    Deliberately dumb — add/get/pop plus the two scans the engine's
    per-step wake pass runs: ``due`` (tool responses that have landed)
    and ``stale`` (KV parked longer than the session TTL, still worth
    holding a handle for).  The engine is not drained while any entry
    exists: a sleeping session owns no slot and sits on no queue, and
    this ledger is the only thing keeping it alive."""

    def __init__(self) -> None:
        self._by_rid: dict[int, SleepEntry] = {}

    def add(self, e: SleepEntry) -> None:
        assert e.rid not in self._by_rid, f"rid {e.rid} already asleep"
        self._by_rid[e.rid] = e

    def get(self, rid: int) -> Optional[SleepEntry]:
        return self._by_rid.get(rid)

    def pop(self, rid: int) -> SleepEntry:
        return self._by_rid.pop(rid)

    def __len__(self) -> int:
        return len(self._by_rid)

    def __contains__(self, rid: int) -> bool:
        return rid in self._by_rid

    def entries(self) -> list[SleepEntry]:
        return list(self._by_rid.values())

    def due(self, now: float) -> list[SleepEntry]:
        """Entries whose scheduled tool response has landed."""
        return [e for e in self._by_rid.values()
                if e.wake_at is not None and e.wake_at <= now]

    def stale(self, now: float, ttl: int) -> list[SleepEntry]:
        """Entries still holding KV that have slept past the TTL."""
        return [e for e in self._by_rid.values()
                if e.state is not None and now - e.slept_step >= ttl]


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ServingEngine:
    """Continuous batching driven by the shared scheduler runtime.

    * a gang (bubble) bursts only when enough slots are free to co-schedule
      it (priorities implement the paper's gang scheduling — Figure 1);
    * prefix-affine requests land in adjacent slots so their shared KV
      prefix stays resident (the data-sharing relation);
    * a starving slot's ``acquire`` runs the hierarchical steal pass — a
      queued gang is pulled whole from a loaded page group, its threads
      flagged for next-touch so the first post-migration admission re-homes
      their KV (batched splice), and the thief pays the cost model's
      admission-latency bill;
    * page-group queue-depth skew feeds the runtime's cost-benefit test and
      triggers one bulk ``rebalance`` when recent steal spend exceeds the
      re-spread bill;
    * a request group that stalls (client backpressure) is *regenerated*:
      pulled out of the slots — its per-slot KV parked — and re-queued as a
      closed bubble, keeping its affinity;
    * with ``pods``/``hosts`` > 1 the slot hierarchy is sharded across
      hosts: steals cross the DCN when nothing nearer has work, priced by
      the cost model's per-level table (``bill_model`` splits what the
      scheduler *believes* a crossing costs from what it *pays* — the
      DCN-naive baseline ranks victims flat and pays real DCN latency);
    * with ``hbm_budget`` set, each KV page group carries a byte budget
      (``kv_bytes`` per resident request): admission skips slots of a full
      group (the gang parks on its queue instead of thrashing), the steal
      survey and the rebalance deal refuse destinations that cannot hold
      the loot, and the ledger in ``hbm_used`` never exceeds a group's
      budget.  ``capacity_aware=False`` keeps the budget enforced but
      discovers fullness only after the claim — loot is dragged (and its
      steal billed) before bouncing back: the measurable capacity-blind
      baseline for ``serve/hbm_pressure_refusal_speedup``;
    * **execution is host-sharded** (``per_host_decode``, default on):
      each host drives its own decode batch — one ``decode_step`` per host
      per engine step over that host's KV shard, skipped when the host's
      batch is empty — and same-length fresh prompts admitted in one wave
      are prefilled in one ``prefill_wave`` call per host
      (``wave_prefill``, default on).  Neither changes a single decoded
      token (slots are independent; property-tested), they change what
      the engine *models*: per-shard step latency and per-host occupancy
      skew (``EngineStats.host_decode_steps`` / ``host_active_slots``)
      instead of one fleet-wide batch no real DCN-sharded deployment
      runs;
    * **rebalancing is DCN-priced** (``dcn_rebalance``, default on): each
      re-spread move is billed by the boundary it crosses through the
      cost model's ``level_table`` (a cross-host move pays the DCN toll,
      not flat ``rebalance_per_move``), and the queue-depth trigger
      compares a machine-wide re-spread against **host-local** ones
      (`BubbleScheduler.rebalance(scope=)`), buying the local page
      shuffle whenever the machine-wide quote is dearer.
      ``dcn_rebalance=False`` keeps the flat-priced, machine-wide-only
      trigger — the measurable baseline for
      ``serve/dcn_rebalance_speedup``.  Single-host fleets have no tabled
      boundary, so both settings are byte-identical there.

    ``mode="admission"`` is the pre-runtime engine: plain admission, no
    steal, no rebalance, first-touch homing.

    Knob units, for the record: every cost-model price is in **engine
    steps** (admission latency); ``hbm_budget``/``kv_bytes`` are in the
    same abstract bytes as each other (only their ratio matters — the
    resident-request count a page group can hold); ``window``/``cooldown``
    are engine steps, ``depth_skew``/``min_backlog`` are queued decode
    threads.
    """

    def __init__(self, cfg, params, *, n_slots: int = 8,
                 cache_len: int = 256, group: int = 4,
                 hosts: int = 1, pods: int = 1,
                 backend=None, mode: str = "runtime",
                 cost_model: StealCostModel = SERVE_COST,
                 bill_model: Optional[StealCostModel] = None,
                 hbm_budget: Optional[float] = None, kv_bytes: float = 1.0,
                 capacity_aware: bool = True,
                 per_host_decode: bool = True, wave_prefill: bool = True,
                 dcn_rebalance: bool = True,
                 host_speed=None, speed_aware: bool = True,
                 gang_split: bool = False,
                 depth_skew: int = 2, window: int = 16,
                 min_backlog: int = 2, cooldown: Optional[int] = None,
                 sla_classes: Optional[dict] = None, preempt: bool = False,
                 preempt_cooldown: int = 8,
                 kv_store=None, kv_restore_level: str = "host",
                 reprefill_unit: float = 0.25,
                 agentic_sleep: bool = True, wake_quote: bool = True,
                 sleep_retain_hbm: bool = False,
                 session_ttl: Optional[int] = None):
        assert mode in ("runtime", "admission"), mode
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.mode = mode
        self.topo = slots_topology(n_slots, group, hosts=hosts, pods=pods)
        if mode == "runtime":
            self.policy = StealPolicy(self.topo, cost_model=cost_model,
                                      bill_model=bill_model)
        else:
            self.policy = BubblePolicy(self.topo, steal=False)
        self.sched = self.policy.sched
        # -- per-page-group HBM ledger (admission control) --
        assert hbm_budget is None or hbm_budget >= kv_bytes, \
            "a page group must hold at least one request's KV"
        self.hbm_budget = hbm_budget
        self.kv_bytes = kv_bytes
        names = self.topo.level_names()
        self._page_idx = names.index("page")
        self._host_idx = names.index("host") if "host" in names else None
        # slot -> global page-group index (its ancestor at the page level)
        self._page_of = [self.topo.cpus[s].path()[self._page_idx].index
                         for s in range(n_slots)]
        # page-group index -> owning host component (None on single host):
        # the rebalance trigger uses it to spot skew that is host-local
        self._page_host = [
            p.path()[self._host_idx] if self._host_idx is not None else None
            for p in self.topo.components("page")]
        self.hbm_used = [0.0] * len(self.topo.components("page"))
        self._slot_charged = [False] * n_slots   # slot holds a reservation
        self.capacity_aware = capacity_aware and hbm_budget is not None
        # -- straggler model: per-host relative decode speed in (0, 1] --
        # ``host_speed[h]`` < 1 makes host h's decode_step span more than
        # one engine step (a speed-credit accumulator in :meth:`step`);
        # ``speed_aware`` additionally lets the scheduler SEE the skew
        # (the costed steal survey and the LPT rebalance deal weigh
        # backlog by host speed through ``speed_of``).  ``speed_aware=
        # False`` with a nonzero skew is the lockstep-assuming baseline:
        # the machine still runs slow, the scheduler still deals to it.
        n_hosts_total = (len(self.topo.components("host"))
                         if self._host_idx is not None else 1)
        if host_speed is not None:
            host_speed = [float(s) for s in host_speed]
            assert len(host_speed) == n_hosts_total, \
                f"host_speed needs one entry per host " \
                f"({len(host_speed)} != {n_hosts_total})"
            assert all(0.0 < s <= 1.0 for s in host_speed), host_speed
            assert per_host_decode or self._host_idx is None, \
                "host_speed on a multi-host fleet needs per_host_decode"
        self.host_speed = host_speed
        self.speed_aware = speed_aware and host_speed is not None
        self._speed_by_host = (
            {id(h): s for h, s in zip(self.topo.components("host"),
                                      host_speed)}
            if host_speed is not None and self._host_idx is not None else {})
        self.gang_split = gang_split
        self.runtime = SchedulerRuntime(
            self.topo, self.policy, on_data_migrate=self._on_kv_migrate,
            can_accept=(self._can_accept
                        if self.capacity_aware and mode == "runtime"
                        else None),
            bytes_of=(self._kv_need if mode == "runtime" else None),
            speed_of=(self._host_speed_of
                      if self.speed_aware and mode == "runtime" else None))
        # this engine bills a rebalance's level-table tolls where the KV
        # lands (admission freezes on the receiving page groups, see
        # _maybe_rebalance), so opt into the scheduler's split billing —
        # consume_cost() then returns the flat trigger-side part only
        self.sched.ingest_billing = True
        self.backend = backend if backend is not None else \
            JaxModelBackend(cfg, params, cache_len)
        # -- host-sharded execution: one decode batch (one backend state
        # shard, one decode_step per engine step) per execution group.
        # With per_host_decode on a multi-host fleet the groups are the
        # hosts' (contiguous) slot ranges; otherwise one group spans the
        # whole fleet — the historical global batch, byte for byte.
        self.per_host_decode = per_host_decode
        self.wave_prefill = wave_prefill
        self.dcn_rebalance = dcn_rebalance
        if per_host_decode and self._host_idx is not None:
            ranges = []
            for h in self.topo.components("host"):
                cpus = [leaf.cpu for leaf in h.leaves()]
                assert cpus == list(range(cpus[0], cpus[-1] + 1)), cpus
                ranges.append((cpus[0], cpus[-1] + 1))
            self._exec_groups = ranges
        else:
            self._exec_groups = [(0, n_slots)]
        self._group_of = [g for g, (lo, hi) in enumerate(self._exec_groups)
                          for _ in range(lo, hi)]   # slot -> exec group
        # per-exec-group decode speed + the speed-credit accumulator: a
        # group decodes when its credit reaches one whole step.  Exec
        # groups are the hosts' slot ranges in host-component order
        # (asserted above when host_speed is given), so index g maps 1:1.
        if host_speed is None:
            self._group_speed = [1.0] * len(self._exec_groups)
        elif len(self._exec_groups) == len(host_speed):
            self._group_speed = list(host_speed)
        else:                        # single exec group (single host)
            self._group_speed = [host_speed[0]]
        self._host_credit = [0.0] * len(self._exec_groups)
        self._states = []
        tok_shards = []
        for lo, hi in self._exec_groups:
            st, tok = self.backend.init(hi - lo)
            self._states.append(st)
            tok_shards.append(tok)
        self.tokens = tok_shards[0] if len(tok_shards) == 1 else \
            np.concatenate(tok_shards, axis=0)
        self.slot_req: list[Optional[Request]] = [None] * n_slots
        self.slot_thread: dict[int, Thread] = {}
        self._reqs: dict[int, Request] = {}
        self._gangs: dict[str, Bubble] = {}
        self._next_rid = 0
        self._kv_park: dict[int, tuple[object, int]] = {}  # rid -> (state, tok)
        self._stall = [0.0] * n_slots     # admission-latency bill per slot
        self._pending: dict[int, Thread] = {}  # claimed, waiting out a stall
        # queue-depth rebalance trigger state (runtime mode only)
        self.depth_skew = depth_skew
        self.min_backlog = min_backlog
        self.window = window
        self.cooldown = window if cooldown is None else cooldown
        self._paid: deque[float] = deque()        # steal cost per step
        self._steps_since_rebalance = self.cooldown   # start armed
        self._cost_mark = 0.0
        # -- SLA tiers (open-loop traffic) --
        # ``sla_classes`` maps class name -> :class:`~repro.serving.
        # workload.SLAClass`; set, it turns on the weighted-deficit
        # round-robin admission gate (a task filter over the covering-list
        # walk), multilevel-feedback demotion, and — with ``preempt`` —
        # KV park/splice preemption of preemptible tiers under
        # ``preempts``-class backlog.  ``None`` (default) is the
        # historical class-blind engine, bit for bit.
        self.sla_classes = dict(sla_classes) if sla_classes else None
        self.preempt = preempt and self.sla_classes is not None
        self.preempt_cooldown = preempt_cooldown
        self._last_preempt = -(10 ** 9)
        # WDRR deficit ledger: classes start with one quantum of credit
        self._wdrr_credit = ({n: float(c.weight)
                              for n, c in self.sla_classes.items()}
                             if self.sla_classes else {})
        # latency ledgers, keyed by CONTRACT class (``Request.sla``;
        # ``None``-classed requests land under "unclassed")
        self._ttft: dict[str, list] = {}
        self._gaps: dict[str, list] = {}
        # -- elastic fleet: KV continuation snapshots + live kill/join --
        # ``kv_store`` is a :class:`~repro.checkpoint.kv_store.KVStore`
        # (duck-typed: due/maybe_snapshot/restore); on its cadence the
        # engine snapshots every resident continuation.  When a host dies
        # (:meth:`kill_host`) each orphan is restored from the snapshot —
        # a ``kv_restore_level`` boundary toll on its KV bytes plus a
        # replay of the tokens emitted since, at ``reprefill_unit`` steps
        # per token — or re-prefilled from scratch (full history at the
        # same per-token rate), whichever the cost model quotes cheaper.
        self.kv_store = kv_store
        self.kv_restore_level = kv_restore_level
        self.reprefill_unit = reprefill_unit
        # -- agentic sessions: tool-call sleep/wake --
        # ``agentic_sleep`` (default): a request hitting a tool-call
        # marker *sleeps* — KV parked, slot freed, thread held in the
        # SleepingLedger until the tool response.  ``False`` is the
        # hold-the-slot baseline: the request keeps its slot (and HBM
        # reservation) idle through the think gap — the measurable
        # contrast for ``serve/agentic_slot_util_speedup``.  Streams are
        # identical either way: a sleep injects no tokens.
        self.agentic_sleep = agentic_sleep
        # ``wake_quote`` arbitrates wake placement (home page group vs
        # the cheapest group under current queue/HBM pressure, the away
        # move priced at cost-model belief and billed at bill-model
        # truth); ``False`` pins every wake to its home group.
        self.wake_quote = wake_quote
        # ``sleep_retain_hbm``: keep the sleeper's KV bytes reserved in
        # its home page group (guaranteed wake-home capacity, paid in
        # admission headroom); default refunds the reservation — parked
        # KV lives host-side, off the budget, like every other park.
        self.sleep_retain_hbm = sleep_retain_hbm
        # ``session_ttl``: engine steps a sleeping session's KV survives
        # before the stale-eviction pass drops it (the wake then pays a
        # full re-prefill).  ``None`` holds KV forever.
        self.session_ttl = session_ttl
        self._sleeping = SleepingLedger()
        self._thinking: dict[int, SleepEntry] = {}   # hold-mode, by slot
        self._wake_lat: dict[str, list] = {}         # wake-to-token ledger
        if kv_store is not None:
            assert mode == "runtime", "kv snapshots need the runtime engine"
            assert callable(getattr(self.backend, "peek", None)), \
                "kv_store needs a backend with a non-mutating peek() " \
                "(the paged backend's extract is a destructive table edit)"
        self._dead_slots: set[int] = set()    # cpu ids of killed hosts
        self._restore_debt: dict[int, float] = {}   # rid -> admission bill
        self._group = group                   # page-group size, for joins
        self._host_group = ({id(h): g for g, h in
                             enumerate(self.topo.components("host"))}
                            if self._host_idx is not None else {})
        self.stats = EngineStats(
            host_decode_steps=[0] * len(self._exec_groups),
            host_active_slots=[0] * len(self._exec_groups),
            host_skipped_steps=[0] * len(self._exec_groups))
        self.steps = 0
        self.completed: list[Request] = []

    # -- client API ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int, *,
               prio: Optional[int] = None, gang: Optional[str] = None,
               home: Optional[str] = None, sla: Optional[str] = None,
               tool_calls: tuple = ()) -> int:
        """Queue one request.  ``home`` names a topology component
        (``"host1"``, ``"page3"``, ...) whose list receives the work — the
        cross-host admission path: a front-end that routes a gang to one
        shard wakes its bubble there, narrowing its scheduling area to
        that subtree; other shards can still reach it, but only by paying
        the steal survey's (DCN-priced) bill.  ``None`` keeps the global
        list (any slot may admit it).  A late joiner to an already-burst
        gang honors its own ``home`` (it lands on that list) and falls
        back to the gang's burst list otherwise — ``home`` always wins
        over where the gang happened to burst.

        ``sla`` labels the request with an SLA class.  On an engine built
        with ``sla_classes`` the class also *schedules*: ``prio`` defaults
        to the class's paper priority (§3.3.2) and the class rides the
        WDRR admission gate; without ``sla_classes`` the label is carried
        for measurement only (the FIFO baseline's requests are judged by
        the same SLOs).

        ``tool_calls`` marks the request agentic: a tuple of
        ``(at_tokens, think_steps)`` markers, ordered by position — when
        the emitted-token count reaches ``at_tokens`` the request blocks
        on a tool response for ``think_steps`` engine steps
        (``think_steps=None`` blocks until :meth:`wake`).  See
        ``agentic_sleep`` for what blocking does to the slot."""
        tool_calls = tuple((int(at), None if think is None else int(think))
                           for at, think in tool_calls)
        last_at = 1
        for at, think in tool_calls:
            assert 1 <= at < max_new_tokens, \
                f"tool call at token {at} outside 1..{max_new_tokens - 1}"
            assert at >= last_at, "tool calls must be ordered by position"
            assert think is None or think >= 1, think
            last_at = at
        if prio is None:
            prio = (self.sla_classes[sla].prio
                    if self.sla_classes and sla in self.sla_classes else 0)
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, np.asarray(prompt, np.int32), max_new_tokens,
                      prio=prio, gang=gang, sla=sla, tier=sla,
                      submit_step=self.steps, tool_calls=tool_calls)
        self._reqs[rid] = req
        t = thread(float(max_new_tokens), name=f"req{rid}", prio=prio,
                   data=gang or f"req{rid}")
        t.request = req                                   # type: ignore
        at = self._home_queue(home)
        if gang is None:
            if at is None:
                self.sched.submit_thread(t)
            else:
                at.push(t)
            return rid
        g = self._gang_bubble(gang, prio)
        g.insert(t)
        if g.burst:
            # the gang already burst: late joiners must land on a live
            # list (inserting into an off-queue burst husk would strand
            # them forever).  The caller's ``home`` wins — the old code
            # silently dropped it and pinned the joiner to the burst
            # list — falling back to the gang's scheduling area
            q = at if at is not None else (
                g.home_list if g.home_list is not None
                else self.sched.queues.global_queue())
            q.push(t)
        elif not self._gang_scheduled(g):
            # fresh gang, or one that completed/was dropped and has new
            # members: (re-)wake it.  The old engine set a sticky ``_woken``
            # flag here, so a finished gang's bubble could never be woken
            # again and later submits to the same gang were lost.
            self.sched.wake_up_bubble(g, at=at)
        elif not self._bubble_queued(g):
            # the gang is live but only through its *members* (a rebalance
            # expanded the closed bubble and dealt them out individually,
            # or they occupy slots) — the bubble itself sits on no list and
            # nothing will ever burst it, so a thread left only inside it
            # is stranded: schedule the late joiner directly, like its
            # expanded siblings — again honoring the caller's ``home``
            q = at if at is not None else (
                g.home_list if g.home_list is not None
                else self.sched.queues.global_queue())
            q.push(t)
        return rid

    def _bubble_queued(self, g: Bubble) -> bool:
        """Whether the bubble object itself sits on some run queue (its
        members being queued individually does not count)."""
        return any(task is g for q in self.sched.queues.queues.values()
                   for task in q.tasks)

    def _home_queue(self, home: Optional[str]):
        """Resolve a component name to its run queue (None = global).

        Submit is the admission hot path, so the name->queue map is built
        once per engine (component names are unique: ``level.name`` +
        index)."""
        if home is None:
            return None
        by_name = getattr(self, "_queues_by_name", None)
        if by_name is None:
            by_name = {q.comp.name: q
                       for q in self.sched.queues.queues.values()}
            self._queues_by_name = by_name
        try:
            return by_name[home]
        except KeyError:
            raise ValueError(f"unknown home component {home!r} "
                             f"(topology: {self.topo.describe()})") from None

    def _gang_bubble(self, gang: str, prio: int) -> Bubble:
        key = f"gang:{gang}"
        b = self._gangs.get(key)
        if b is None:
            # gang bubbles less prioritised than their threads => they burst
            # only when running threads can't fill the slots (Figure 1)
            b = bubble(name=key, prio=prio - 1, burst_level="page")
            self._gangs[key] = b
        return b

    def _gang_scheduled(self, g: Bubble) -> bool:
        """Whether the scheduler still owns the gang: the closed bubble (or
        any of its tasks) sits on some list, or a member occupies a slot."""
        for q in self.sched.queues.queues.values():
            for task in q.tasks:
                if task is g or task.root() is g:
                    return True
        return any(t.parent is g for t in self.slot_thread.values()) or \
            any(t.parent is g for t in self._pending.values())

    # -- KV homing (the data policy's physical side) --------------------------
    def _on_kv_migrate(self, data: str, old_slot: int, new_slot: int) -> None:
        self.stats.kv_migrations += 1
        names = self.topo.level_names()
        common = names.index(self.topo.common_level(old_slot, new_slot).name)
        if common < self._page_idx:
            self.stats.kv_page_moves += 1      # crossed KV page groups
        if self._host_idx is not None and common < self._host_idx:
            self.stats.kv_host_moves += 1      # crossed hosts: DCN traffic

    # -- the per-page-group HBM ledger (admission control) ---------------------
    def _headroom(self, page: int) -> float:
        """Unreserved HBM bytes left in one page group's budget."""
        if self.hbm_budget is None:
            return float("inf")
        return self.hbm_budget - self.hbm_used[page]

    def _charge(self, slot: int) -> None:
        """Reserve one request's KV bytes in the slot's page group — at
        *claim* time, so a stolen thread waiting out its admission stall in
        ``_pending`` cannot be overcommitted by later claims."""
        if not self._slot_charged[slot]:
            self.hbm_used[self._page_of[slot]] += self.kv_bytes
            self._slot_charged[slot] = True

    def _refund(self, slot: int) -> None:
        """Release the slot's reservation (request finished, parked, or
        folded back into a regenerated gang)."""
        if self._slot_charged[slot]:
            self.hbm_used[self._page_of[slot]] -= self.kv_bytes
            self._slot_charged[slot] = False

    def _kv_need(self, task) -> float:
        """KV bytes one task would occupy: whole gangs need room for every
        live member — stealing a gang a group cannot finish admitting
        would strand the tail."""
        if isinstance(task, Bubble):
            live = sum(1 for th in task.threads() if th.remaining > 0)
            return self.kv_bytes * max(live, 1)
        return self.kv_bytes

    def _host_speed_of(self, comp) -> float:
        """The scheduler's speed ruler: relative decode speed of the host
        owning ``comp`` (a page group, a slot, or the host list itself).
        Components above the host level — the machine-wide lists — have no
        one owner and run at nominal speed."""
        if not self._speed_by_host:
            return 1.0
        h = self.topo.ancestor_at(comp, "host")
        return self._speed_by_host[id(h)] if h is not None else 1.0

    def _can_accept(self, cpu: int, task, pending=()) -> bool:
        """The scheduler's capacity veto: can ``cpu``'s page group hold the
        loot's KV on top of what a bulk deal already routed there
        (``pending``)?  A full page group refuses and the survey/deal
        looks elsewhere."""
        need = self._kv_need(task) + sum(self._kv_need(p) for p in pending)
        return self._headroom(self._page_of[cpu]) >= need - 1e-9

    # -- SLA-class admission: weighted deficit round-robin --------------------
    @staticmethod
    def _live_thread(th) -> bool:
        """A queued thread that still has decoding to do (the opposite of
        a finished-gang husk awaiting collection)."""
        req = getattr(th, "request", None)
        return th.remaining > 0 and (req is None or not req.done)

    @staticmethod
    def _tier_of(th) -> Optional[str]:
        req = getattr(th, "request", None)
        return req.tier if req is not None else None

    def _queued_by_class(self) -> dict[str, int]:
        """Live queued decode threads per scheduling tier (slot-resident
        and ``_pending`` work is already admitted and does not count)."""
        counts = {n: 0 for n in self.sla_classes}
        for q in self.sched.queues.queues.values():
            for task in q.tasks:
                ths = task.threads() if isinstance(task, Bubble) else (task,)
                for th in ths:
                    if self._live_thread(th):
                        tier = self._tier_of(th)
                        if tier in counts:
                            counts[tier] += 1
        return counts

    def _wdrr_replenish(self, queued: set) -> None:
        """Start a new deficit round: every backlogged class earns its
        ``weight`` in credit (capped at 4x as a safety bound — credit is
        only ever granted when the whole round is spent, so in practice a
        class carries at most one quantum plus change)."""
        for n in queued:
            cls = self.sla_classes[n]
            self._wdrr_credit[n] = min(
                self._wdrr_credit.get(n, 0.0) + cls.weight,
                4.0 * cls.weight)

    def _wdrr_gate(self) -> Optional[set]:
        """One admission wave's deficit-round-robin bookkeeping.

        Classic DRR adapted to a priority walk: credit is replenished
        only when **every** backlogged class has spent its quantum (a new
        round) — NOT every wave, or a high-priority class spending at
        most the slot count per wave would re-earn it each time and the
        gate would degenerate to pure priority, starving ``batch``
        exactly the way the WDRR exists to prevent.  Between rounds a
        class out of credit is invisible to the covering-list walk, which
        is how lower tiers get their turn.  An idle class keeps at most
        one quantum (no banking a burst of credit to lock the batch
        later).  Returns the eligible-class set (backlogged AND holding
        >=1 credit; never empty while work is queued — the gate decides
        *whose* work goes first, never idles a slot), or ``None`` when no
        class has queued work."""
        counts = self._queued_by_class()
        queued = {n for n, c in counts.items() if c}
        for n, cls in self.sla_classes.items():
            if n not in queued:
                self._wdrr_credit[n] = min(self._wdrr_credit.get(n, 0.0),
                                           float(cls.weight))
        if not queued:
            return None
        elig = {n for n in queued if self._wdrr_credit[n] >= 1.0}
        if not elig:
            self._wdrr_replenish(queued)
            elig = {n for n in queued if self._wdrr_credit[n] >= 1.0}
        return elig if elig else set(queued)

    def _wdrr_filter(self, elig: set):
        """The task filter the eligible-class set puts on the covering-list
        walk.  Classless tasks always pass.  Stale husks (finished
        threads, empty or all-done bubbles) must ALSO pass: they carry no
        work to gate, and hiding them from the lookup would leave them
        stuck on their queues forever — ``_drained()`` would never see an
        empty machine.  The admit loop drops them on sight instead."""
        def ok(task) -> bool:
            if isinstance(task, Bubble):
                live = [th for th in task.threads() if self._live_thread(th)]
                if not live:
                    return True             # husk: keep it collectable
                return any(self._tier_of(th) is None
                           or self._tier_of(th) in elig for th in live)
            if not self._live_thread(task):
                return True                 # husk: keep it collectable
            tier = self._tier_of(task)
            return tier is None or tier in elig
        return ok

    def _wdrr_spend(self, t: Thread, elig: set) -> None:
        """Bill one admission against its class's deficit; a class out of
        credit leaves the eligible set, and when the last one does a new
        round replenishes every still-backlogged class (work conservation
        — recomputed in place so the same wave's later slots see it)."""
        tier = self._tier_of(t)
        if tier is None or tier not in self._wdrr_credit:
            return
        self._wdrr_credit[tier] -= 1.0
        if self._wdrr_credit[tier] < 1.0 and tier in elig:
            elig.discard(tier)
            if not elig:
                counts = self._queued_by_class()
                queued = {n for n, c in counts.items() if c}
                self._wdrr_replenish(queued)
                elig.update(n for n in queued
                            if self._wdrr_credit[n] >= 1.0)
                if not elig:
                    elig.update(queued)

    # -- latency ledger -------------------------------------------------------
    def _note_first_token(self, req: Request, now: float) -> None:
        """Stamp the request's TTFT at its prefill token.  Inherently
        stall-aware: prefill runs at *actual* admission, after any WDRR
        gating, queueing, and billed steal/rebalance stalls."""
        if req.first_token_step is None:
            req.first_token_step = int(now)
            req.last_token_step = int(now)
            self._ttft.setdefault(req.sla or "unclassed", []).append(
                int(now) - req.submit_step)

    def _note_token(self, req: Request, now: float) -> None:
        """Record one decode token's inter-token gap (engine steps since
        the previous token — >1 means the request sat out stalled steps).

        A request with multiple service intervals (parked, preempted, or
        asleep on a tool call, then spliced back) must NOT count the
        break as an inter-token gap — the old ledger did, so one sleeping
        session's think time double-counted as a monster token gap AND
        sat in the percentiles of a class that was never being served.
        The first token after a resume is flagged (``service_break``) and
        recorded in the wake-to-token ledger instead when the break was a
        wake (``wake_step`` set: tool response -> first token, the
        latency an agentic user actually feels), or dropped entirely for
        scheduler-imposed parks."""
        if req.service_break:
            req.service_break = False
            if req.wake_step is not None:
                self._wake_lat.setdefault(req.sla or "unclassed", []).append(
                    int(now) - req.wake_step)
                req.wake_step = None
        elif req.last_token_step is not None:
            self._gaps.setdefault(req.sla or "unclassed", []).append(
                int(now) - req.last_token_step)
        req.last_token_step = int(now)

    def latency_summary(self) -> dict:
        """Per-class arrival-time latency percentiles + goodput-under-SLA.

        TTFT and inter-token gaps are in engine steps, aggregated with the
        deterministic nearest-rank percentile; ``goodput`` counts completed
        requests whose TTFT met their contract class's SLO (see
        :func:`repro.serving.workload.goodput_under_sla`).

        TTFT is judged on the *first* admission only (``_note_first_token``
        never re-stamps a resumed request); re-woken service intervals
        report separately as ``wake_p50``/``wake_p99`` — tool response to
        first post-wake token — with ``wakes`` the sample count."""
        out: dict = {"classes": {}}
        for name in sorted(set(self._ttft) | set(self._gaps)
                           | set(self._wake_lat)):
            t = self._ttft.get(name, [])
            g = self._gaps.get(name, [])
            w = self._wake_lat.get(name, [])
            out["classes"][name] = {
                "n": len(t),
                "ttft_p50": percentile(t, 50),
                "ttft_p99": percentile(t, 99),
                "tok_p50": percentile(g, 50),
                "tok_p99": percentile(g, 99),
                "wakes": len(w),
                "wake_p50": percentile(w, 50),
                "wake_p99": percentile(w, 99),
            }
        if self.sla_classes:
            good, total = goodput_under_sla(self.completed, self.sla_classes)
        else:
            good, total = goodput_under_sla(self.completed)
        out["goodput"] = {"good": good, "total": total,
                          "frac": good / total if total else 1.0}
        return out

    # -- slot management ------------------------------------------------------
    def _admit(self, now: float) -> None:
        """Fill free slots from the runtime; batch every KV write.

        Parked requests (regenerated, possibly stolen meanwhile) are
        restored with a *splice* of their saved state — the next-touch
        re-home — instead of a re-prefill; fresh requests run prefill.
        All resulting single-slot states are written in one batched
        splice at the end.

        A scheduler call that accrued cost (a successful steal's remote
        lock/KV drag) stalls its slot: the claimed thread waits in
        ``_pending`` and enters the slot only once the admission-latency
        bill is paid — the slot never holds a half-migrated request whose
        state the whole-batch decode would advance."""
        writes: list[tuple[int, object]] = []
        # (exec group, prompt len) -> [(slot, req)]: fresh prompts grouped
        # into one wave-batched prefill call per host per length
        fresh: dict[tuple[int, int], list] = {}
        with span("engine.schedule") as sp:
            mark = self._sched_mark() if sp is not None else None
            self._claim(now, writes, fresh, sp)
            if sp is not None:
                self._sched_note(sp, mark)
        # wave-batched prefill: the per-request loop this replaces ran one
        # model call per fresh prompt; the splice below was already batched
        for (_, length), batch in fresh.items():
            with span("engine.prefill") as sp:
                if sp is not None:
                    sp.update(rids=[req.rid for _, req in batch],
                              n=len(batch), length=length)
                results = self.backend.prefill_wave(
                    [req.prompt for _, req in batch])
                self.stats.prefill_waves += 1
                for (slot, req), (tok, st) in zip(batch, results):
                    req.out_tokens.append(tok)
                    self._note_first_token(req, now)
                    self.tokens[slot, 0] = tok
                    self.stats.prefills += 1
                    writes.append((slot, st))
        if writes:
            # one batched splice per host batch (execution group): each
            # group's KV shard is written in a single traversal
            by_group: dict[int, list[tuple[int, object]]] = {}
            for slot, st in writes:
                g = self._group_of[slot]
                lo = self._exec_groups[g][0]
                by_group.setdefault(g, []).append((slot - lo, st))
            for g, pairs in by_group.items():
                with span("engine.splice") as sp:
                    if sp is not None:
                        lo = self._exec_groups[g][0]
                        sp.update(rids=[self.slot_req[lo + s].rid
                                        for s, _ in pairs], n=len(pairs))
                    self._states[g] = self.backend.splice(self._states[g],
                                                          pairs)
                self.stats.kv_splices += 1
            self.stats.kv_spliced_slots += len(writes)

    def _claim(self, now: float, writes: list, fresh: dict,
               sp: Optional[dict]) -> None:
        """``_admit``'s claim loop: each free slot asks the runtime for
        work; a parked request queues its splice in ``writes``, a fresh
        one its prefill in ``fresh``.  ``sp`` (a span's info, or None)
        collects the claimed request ids."""
        # SLA gate: one WDRR replenish per admission wave; the resulting
        # eligible-class set rides the covering-list walk as a task filter
        # and is spent/recomputed in place as the wave's slots admit
        elig = self._wdrr_gate() if self.sla_classes else None
        filt = self._wdrr_filter(elig) if elig is not None else None
        for slot in range(self.n_slots):
            if self.slot_req[slot] is not None or self._stall[slot] > 0 \
                    or slot in self._dead_slots:
                continue
            t = self._pending.pop(slot, None)
            if t is None:
                full = self._headroom(self._page_of[slot]) \
                    < self.kv_bytes - 1e-9
                # HBM admission control: a slot of a page group at its
                # budget does not even run the scheduler call — the queued
                # gang *parks* where it is (another group's slot, or time,
                # will take it) instead of claiming KV it cannot splice in
                if full and self.capacity_aware:
                    if self.sched.queues.total_tasks():
                        self.stats.hbm_slot_waits += 1
                    continue
                # keep acquiring past stale husks: a finished-gang thread
                # (remaining 0 / request done) is dropped on sight and the
                # SAME slot looks again in the SAME wave — the old code
                # bailed after one husk and idled the slot a whole step
                # with live work still queued
                while True:
                    t, cost = self.runtime.acquire(slot, now,
                                                   task_filter=filt)
                    if cost:
                        self._stall[slot] += cost
                        self.stats.stall_steps += cost
                    if t is None or self._live_thread(t):
                        break
                    self.runtime.release(slot, t, True, now)   # husk: drop
                if t is None:
                    continue
                if elig is not None:
                    self._wdrr_spend(t, elig)
                if full:
                    # capacity-blind baseline: fullness is discovered only
                    # at splice time, *after* the claim (and after any
                    # steal dragged the loot here and billed its stall).
                    # The request bounces back onto the page's list — the
                    # thrash the capacity-aware survey exists to avoid.
                    self.stats.hbm_refusals += 1
                    self.runtime.release(slot, t, False, now)
                    self.sched.queues.covering(slot)[1].push(t)
                    continue
                self._charge(slot)            # reserve the KV bytes now
                if self._restore_debt:
                    # an orphan of a killed host pays its quoted restore /
                    # re-prefill bill here, at re-admission — the recovery
                    # compute lands as admission latency, like every other
                    # cost in the engine
                    req0 = getattr(t, "request", None)
                    debt = self._restore_debt.pop(req0.rid, 0.0) \
                        if req0 is not None else 0.0
                    if debt:
                        self._stall[slot] += debt
                        self.stats.stall_steps += debt
                if self._stall[slot] > 0:     # pay the migration first
                    self._pending[slot] = t
                    continue
            req: Request = t.request                      # type: ignore
            self.slot_req[slot] = req
            self.slot_thread[slot] = t
            if sp is not None:
                sp.setdefault("rids", []).append(req.rid)
            # data policy: first/next-touch homing of the gang's KV pages
            self.runtime.touch(slot, t)
            parked = self._kv_park.pop(req.rid, None)
            if parked is not None:
                st, tok = parked
                self.tokens[slot, 0] = tok    # resume the continuation
                req.service_break = True      # next token is not a gap
                writes.append((slot, st))
            elif self.wave_prefill:
                # defer: fresh prompts of one wave batch into one prefill
                # call per (host, prompt length) — see below
                key = (self._group_of[slot], len(req.prompt))
                fresh.setdefault(key, []).append((slot, req))
            else:
                with span("engine.prefill") as sp:
                    if sp is not None:
                        sp.update(rids=[req.rid], n=1,
                                  length=len(req.prompt))
                    tok, st = self.backend.prefill(req.prompt)
                req.out_tokens.append(tok)
                self._note_first_token(req, now)
                self.tokens[slot, 0] = tok
                self.stats.prefills += 1
                writes.append((slot, st))

    def _evict(self, slot: int, now: float) -> None:
        req = self.slot_req[slot]
        if req is not None:
            req.done = True
            req.finish_step = int(now)
            self.completed.append(req)
        self.slot_req[slot] = None
        t = self.slot_thread.pop(slot, None)
        if t is not None:
            # the prefill token counts toward max_new_tokens but never
            # decremented `remaining`; zero it so a later gang regeneration
            # cannot resurrect the finished thread
            t.remaining = 0.0
            self.runtime.release(slot, t, True, now)
        self._refund(slot)                    # its KV bytes leave the budget
        rel = getattr(self.backend, "release", None)
        if rel is not None:
            # paged backends reclaim the slot's KV pages on eviction (a
            # metadata edit); dense backends have nothing to free
            g = self._group_of[slot]
            self._states[g] = rel(self._states[g],
                                  slot - self._exec_groups[g][0])
        self.tokens[slot, 0] = 0              # freed slot: no stale decode

    # -- multilevel-feedback demotion + SLA preemption ------------------------
    def _maybe_demote(self, req: Request, t: Thread) -> None:
        """Multilevel-feedback rule: a request that has decoded past its
        scheduling tier's ``demote_after`` sinks to ``demote_to`` — it
        stops competing (WDRR, priority, preemption shielding) as the
        short job it no longer is.  The CONTRACT class (``req.sla``) never
        changes: the ledger still judges it by what was promised."""
        if not self.sla_classes:
            return
        cls = self.sla_classes.get(req.tier) if req.tier else None
        if (cls is None or cls.demote_after is None
                or len(req.out_tokens) < cls.demote_after
                or cls.demote_to not in self.sla_classes):
            return
        req.tier = cls.demote_to
        t.prio = self.sla_classes[req.tier].prio
        self.stats.demotions += 1

    def _park_request(self, slot: int, now: float) -> None:
        """Single-request preemption: extract the slot's KV state and last
        token into ``_kv_park`` (the later re-admission resumes the
        continuation via the batched splice — no re-prefill), free the
        slot, and re-queue the thread on its page group's list so the
        resume finds its KV-affine slots first.  The gang-sized variant is
        :meth:`regenerate_gang` (parks every member, re-queues the closed
        bubble)."""
        req = self.slot_req[slot]
        t = self.slot_thread.pop(slot)
        self.slot_req[slot] = None
        g = self._group_of[slot]
        with span("engine.extract") as sp:
            if sp is not None:
                sp["rid"] = req.rid
            handle = self.backend.extract(self._states[g],
                                          slot - self._exec_groups[g][0])
        self._kv_park[req.rid] = (handle, int(self.tokens[slot, 0]))
        self.stats.kv_parks += 1
        self.tokens[slot, 0] = 0
        self._refund(slot)    # parked KV lives host-side, off the budget
        self.runtime.release(slot, t, False, now)
        self.sched.queues.covering(slot)[1].push(t)

    def _maybe_preempt(self, now: float) -> None:
        """Under pressure, park a preemptible tier's work to admit an
        urgent class: fires when a ``preempts`` class has live queued work,
        no slot is free to take it, and the cooldown has elapsed.  One
        victim per firing — the preemptible gang (or lone request) with
        the most remaining decode, so the freed capacity is reclaimed for
        the longest.  Victims are parked via the KV park/splice path and
        resume later exactly where they left off."""
        if not self.preempt:
            return
        if self.steps - self._last_preempt <= self.preempt_cooldown:
            return
        urgent = {n for n, c in self.sla_classes.items() if c.preempts}
        if not urgent:
            return
        counts = self._queued_by_class()
        if not any(counts.get(n, 0) for n in urgent):
            return
        if any(self.slot_req[s] is None and self._stall[s] <= 0
               and s not in self._pending and s not in self._dead_slots
               for s in range(self.n_slots)):
            return          # a slot opens this wave anyway: no parking
        # victim survey: preemptible-tier residents, gangs counted whole
        best = None                  # (remaining, "gang"/"solo", payload)
        gang_slots: dict[str, list[int]] = {}
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is None or req.done or s in self._thinking:
                continue          # thinking slots hold no spliceable state
            cls = self.sla_classes.get(req.tier) if req.tier else None
            if cls is None or not cls.preemptible:
                continue
            if req.gang is not None:
                gang_slots.setdefault(req.gang, []).append(s)
            else:
                rem = req.max_new_tokens - len(req.out_tokens)
                if rem > 0 and (best is None or rem > best[0]):
                    best = (rem, "solo", s)
        for gname, slots in gang_slots.items():
            rem = sum(self.slot_req[s].max_new_tokens
                      - len(self.slot_req[s].out_tokens) for s in slots)
            if rem > 0 and (best is None or rem > best[0]):
                best = (rem, "gang", gname)
        if best is None:
            return
        if best[1] == "gang":
            self.stats.preempt_parks += self.regenerate_gang(best[2])
        else:
            self._park_request(best[2], now)
            self.stats.preempt_parks += 1
        self.stats.preemptions += 1
        self._last_preempt = self.steps

    # -- queue-depth rebalance trigger ----------------------------------------
    def _page_depths(self) -> list[int]:
        """Runnable decode threads pinned under each page group's lists
        (work on the global list is reachable by every slot and is not
        skew)."""
        depths = []
        for comp in self.topo.components("page"):
            n = 0
            for sub in self.sched._bfs(comp):
                for task in self.sched.queues.queue_of(sub).tasks:
                    if isinstance(task, Bubble):
                        n += sum(1 for th in task.threads()
                                 if th.remaining > 0)
                    elif task.remaining > 0:
                        n += 1
            depths.append(n)
        return depths

    _NO_SCOPE = object()       # sentinel: no re-spread is worth buying

    def _rebalance_candidates(self, depths: list[int]) -> list:
        """Candidate re-spread scopes, most local first: every host whose
        *own* page depths are skewed (a host-local re-spread can fix those
        without quoting a single DCN crossing), then the whole machine
        (``None``).  The flat mode — and any single-host fleet — only ever
        has the machine-wide candidate."""
        cands = []
        if self.dcn_rebalance and self._host_idx is not None:
            # grouped by the host COMPONENT itself, not by round-tripping
            # ``component.index`` through ``topo.components("host")`` — the
            # old lookup silently assumed ``.index`` equals list position,
            # which nothing in Topology guarantees to a consumer; keying by
            # identity scopes the re-spread to the exact component whose
            # pages are skewed on any pod/host layout, ragged or not
            by_host: dict[int, tuple] = {}   # id(host) -> (host, depths)
            for p, d in enumerate(depths):
                h = self._page_host[p]
                by_host.setdefault(id(h), (h, []))[1].append(d)
            for h, ds in by_host.values():
                if len(ds) >= 2 and max(ds) - min(ds) >= self.depth_skew:
                    cands.append(h)
        cands.append(None)
        return cands

    def _choose_rebalance_scope(self, depths: list[int], paid: float):
        """Pick the cheapest re-spread worth buying, or ``_NO_SCOPE``.

        With ``dcn_rebalance`` each candidate is quoted through
        :meth:`BubbleScheduler.estimate_rebalance` — every prospective
        move priced by the boundary it crosses via the cost model's
        ``level_table`` — and the cheapest worthwhile quote wins, ties to
        the most local.  That is the whole point of the mode: when remote
        backlog makes the machine-wide quote dear (per-move DCN tolls), a
        host-local page shuffle that fixes the *local* skew is bought
        instead.  Flat mode keeps the historical single machine-wide test
        (flat per-move estimate), bit for bit."""
        if not self.dcn_rebalance:
            # flat mode: the historical single machine-wide test, bit for
            # bit (flat per-move estimate via queued_movable)
            if self.runtime.rebalance_worth_it(
                    paid, min_backlog=self.min_backlog, level="page"):
                return None
            return self._NO_SCOPE
        if paid <= self.sched.cost_model.rebalance_base:
            return self._NO_SCOPE           # cannot cover even the base
        best, best_cost = self._NO_SCOPE, None
        for scope in self._rebalance_candidates(depths):
            # one quote per candidate: worth-it test AND ranking read the
            # same estimate (quoting replays the whole LPT deal — doing
            # it twice per candidate would double the trigger's hot-path
            # work for nothing)
            movable, est = self.sched.estimate_rebalance("page", scope)
            if movable < self.min_backlog or paid <= est:
                continue
            if best_cost is None or est < best_cost:
                best, best_cost = scope, est
        return best

    def _maybe_rebalance(self, now: float) -> None:
        """Decode-gang queue depths feed the same cost-benefit test the
        adaptive simulator policy uses: when one page group's backlog
        outruns another's by ``depth_skew`` and the steal cost recently
        paid exceeds one bulk re-spread's bill, re-spread across the page
        groups instead of letting slots drain the skew one costed steal at
        a time.  Under ``dcn_rebalance`` the re-spread itself is chosen by
        quote: host-local when the machine-wide deal would pay DCN tolls
        the local fix avoids (:meth:`_choose_rebalance_scope`)."""
        if self.mode != "runtime":
            return
        s = self.sched.stats
        self._paid.append(s.steal_cost - self._cost_mark)
        self._cost_mark = s.steal_cost
        if len(self._paid) > self.window:
            self._paid.popleft()
        self._steps_since_rebalance += 1
        if self._steps_since_rebalance < self.cooldown:
            return
        depths = self._page_depths()
        if len(depths) < 2 or max(depths) - min(depths) < self.depth_skew:
            return
        scope = self._choose_rebalance_scope(depths, sum(self._paid))
        if scope is self._NO_SCOPE:
            return
        # bill the re-spread to (a slot of) the emptiest page group in the
        # chosen scope — the one whose starvation triggered it.  The
        # scheduler accrues the cost for its *next* consume_cost() caller,
        # which outside an acquire would be an arbitrary slot; drain it
        # here and stall the triggering slot explicitly instead.
        pages = [p for p in range(len(depths))
                 if scope is None or self._page_host[p] is scope]
        page = min(pages, key=depths.__getitem__)
        slot = next(iter(self.topo.components("page")[page].leaves())).cpu
        self.runtime.rebalance(slot, now, level="page", scope=scope)
        cost = self.policy.consume_cost()
        if cost:
            self._stall[slot] += cost
            self.stats.stall_steps += cost
        # the DCN side of the bill lands where the KV lands: every slot of
        # a page group that received boundary-crossing loot waits out the
        # transfer (the group's level-table toll) before its next
        # admission — a machine-wide re-spread that scatters work across
        # hosts freezes admissions fleet-wide, which is exactly why the
        # priced trigger above prefers the host-local fix.  Single-host
        # deals cross no tabled boundary: ingest is empty, nothing stalls.
        for comp_name, extra in self.sched.stats.last_rebalance_ingest.items():
            for leaf in self.topo.component(comp_name).leaves():
                self._stall[leaf.cpu] += extra
                self.stats.stall_steps += extra
        self.stats.rebalances += 1
        if scope is not None:
            self.stats.local_rebalances += 1
        self._paid.clear()
        self._cost_mark = self.sched.stats.steal_cost
        self._steps_since_rebalance = 0

    # -- HBM-aware gang splitting ----------------------------------------------
    def _split_wait_quote(self, page_comp, deficit: float) -> float:
        """Engine steps until page group ``page_comp`` frees ``deficit``
        KV bytes by residents finishing on their own — the park-and-wait
        alternative a gang split is quoted against.  The k-th soonest
        resident completion covers a k-reservation deficit; a group
        without enough residents to ever free it quotes infinite.  (Takes
        the component itself: after an elastic ``kill_host`` a component's
        ``.index`` no longer equals its ``components("page")`` position,
        so positional round-trips would quote the wrong group.)"""
        k = int(np.ceil(deficit / self.kv_bytes - 1e-9))
        if k <= 0:
            return 0.0
        rems = sorted(
            req.max_new_tokens - len(req.out_tokens)
            for leaf in page_comp.leaves()
            if (req := self.slot_req[leaf.cpu]) is not None and not req.done)
        if len(rems) < k:
            return float("inf")
        return float(rems[k - 1])

    def _maybe_split_gang(self, now: float) -> None:
        """When the HBM ledger refuses a whole-gang admission, quote
        splitting the gang across sibling page groups of its host against
        parking until the home group drains, and buy the cheaper.

        The stuck state this resolves: a closed gang bubble homed on a
        page-level list whose group cannot hold every live member.  The
        group's own slots skip their scheduler calls (capacity-aware
        admission), and every other group's steal survey refuses the
        bubble whole (``_can_accept`` needs the full gang's KV), so
        without this pass the gang waits for its home group to drain —
        correct, but not always cheapest.  The split is the paper's
        bubble-burst semantics applied one level early: the bubble is
        expanded onto its host's list (scheduling area widened one
        level), members that fit stay on the home group, and the overflow
        is re-homed to the siblings with headroom.  The quote prices each
        re-homed member's ``page`` crossing at ``cost_model`` (belief)
        prices — byte-priced under a bandwidth table, since what moves is
        KV — and the bill lands at ``bill_model`` (machine) prices as
        admission stalls, transfer tolls on the receiving groups."""
        if not self.gang_split or self.mode != "runtime" \
                or self.hbm_budget is None:
            return
        for page_comp in self.topo.components("page"):
            q = self.sched.queues.queue_of(page_comp)
            for b in list(q.tasks):
                if not isinstance(b, Bubble) or b.burst or b.done():
                    continue
                live = [th for th in b.threads() if self._live_thread(th)]
                if not live:
                    continue
                need = self.kv_bytes * len(live)
                if need <= self._headroom(page_comp.index) + 1e-9:
                    continue          # fits whole: normal burst admission
                self._split_gang(b, q, page_comp, live, now)

    def _split_gang(self, b: Bubble, q, page_comp, live: list, now: float
                    ) -> None:
        """Quote and (when cheaper than waiting) commit one gang split."""
        kv = self.kv_bytes
        host = self.topo.ancestor_at(page_comp, "host") or self.topo.root
        sibs = [c for c in self.topo.components("page")
                if c is not page_comp and host in c.path()]
        room = {id(c): self._headroom(c.index) for c in sibs}
        fit_home = int((self._headroom(page_comp.index) + 1e-9) // kv)
        plan: list[tuple] = []        # (member, destination page group)
        for th in live[fit_home:]:
            dest = max(sibs, key=lambda c: room[id(c)], default=None)
            if dest is None or room[id(dest)] < kv - 1e-9:
                return       # siblings cannot absorb the overflow: park
            room[id(dest)] -= kv
            plan.append((th, dest))
        cm = self.sched.cost_model
        split_quote = sum(
            cm.rebalance_move_cost(
                self.topo.crossing_between(page_comp, dest), kv)
            for _, dest in plan)
        deficit = kv * len(live) - self._headroom(page_comp.index)
        if split_quote >= self._split_wait_quote(page_comp, deficit):
            return                    # waiting is quoted cheaper: park
        # buy the split: expand the bubble one level up (its regeneration
        # home is now the host's list) with explicit member placement
        q.remove(b)
        b.burst = True
        b.released_at = now
        b.home_list = self.sched.queues.queue_of(host)
        for th in live[:fit_home]:
            q.push(th)
        for th, dest in plan:
            self.sched.queues.queue_of(dest).push(th)
        # the bill, at machine (bill_model) prices: the flat descriptor
        # part stalls the home group's first slot (whose refused admission
        # triggered the quote); each receiving group's slots wait out the
        # byte-priced transfer toll of the KV dealt into it — the same
        # split billing discipline as `_maybe_rebalance`'s ingest side
        bm = self.sched.bill_model
        flat = bm.rebalance_per_move * len(plan)
        if flat > 0:
            home_slot = next(iter(page_comp.leaves())).cpu
            self._stall[home_slot] += flat
            self.stats.stall_steps += flat
        tolls: dict[int, tuple] = {}      # id(dest) -> (dest, toll)
        for th, dest in plan:
            move = bm.rebalance_move_cost(
                self.topo.crossing_between(page_comp, dest), kv)
            extra = move - bm.rebalance_per_move
            if extra > 0:
                prev = tolls.get(id(dest), (dest, 0.0))[1]
                tolls[id(dest)] = (dest, prev + extra)
        for dest, toll in tolls.values():
            for leaf in dest.leaves():
                self._stall[leaf.cpu] += toll
                self.stats.stall_steps += toll
        self.stats.gang_splits += 1
        self.stats.gang_split_members += len(plan)

    # -- the decode loop -------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: consider a rebalance, admit, decode one
        token for every occupied unstalled slot, retire finished requests.
        Returns #slots decoded.

        Decode is driven **per host batch**: each execution group with any
        occupied slot gets its own ``decode_step`` over its own KV shard
        (one jit per host batch on the jax backend); a host whose batch is
        empty this step skips the call entirely.  Slots are independent in
        every backend, so the union of per-host calls decodes exactly what
        one global call would — sharding execution models per-shard
        latency without touching the streams.

        With a span log attached (``repro.core.trace``) the step is the
        root span ``engine.step``; its phases are ``engine.schedule``,
        ``engine.prefill``, ``engine.splice``, ``engine.decode``,
        ``engine.retire`` and ``engine.extract``, whose ``info`` names
        the requests they touched."""
        with span("engine.step") as sp:
            live = self._step()
            if sp is not None:
                sp.update(step=self.steps - 1, live=live)
            return live

    def _step(self) -> int:
        now = float(self.steps)
        self.steps += 1
        if self.kv_store is not None:
            self._maybe_snapshot_kv(int(now))
        with span("engine.schedule") as sp:
            mark = self._sched_mark() if sp is not None else None
            if self._sleeping or self._thinking:
                # tool responses land before admission, so a woken session
                # can re-enter a slot (and decode) in the very step it wakes
                self._process_wakes(now)
            self._maybe_rebalance(now)
            self._maybe_preempt(now)
            if sp is not None:
                self._sched_note(sp, mark)
        self._admit(now)
        # after admission, so the ledger reflects what actually occupies
        # each group — a pre-admission check would quote deficits against
        # reservations the same wave's claims are about to take
        with span("engine.schedule") as sp:
            mark = self._sched_mark() if sp is not None else None
            self._maybe_split_gang(now)
            if sp is not None:
                self._sched_note(sp, mark)
        active = [s for s in range(self.n_slots)
                  if self.slot_req[s] is not None
                  and s not in self._thinking]
        if self._thinking:
            # the hold-the-slot cost, in its own currency: occupied slots
            # decoding nothing while their session waits on a tool
            self.stats.hold_slot_steps += len(self._thinking)
        for s in range(self.n_slots):
            if self._stall[s] > 0:
                self._stall[s] = max(0.0, self._stall[s] - 1.0)
        if not active:
            return 0
        for g, (lo, hi) in enumerate(self._exec_groups):
            active_g = [s for s in active if lo <= s < hi]
            if not active_g:
                continue                     # idle host: no decode launched
            # straggler model: a host earns ``speed`` credit per engine
            # step its batch is occupied and decodes only on a whole
            # credit — a 0.5x host's decode_step spans two engine steps.
            # Nominal speed earns exactly 1.0 per step: bit-identical.
            self._host_credit[g] += self._group_speed[g]
            if self._host_credit[g] < 1.0 - 1e-9:
                self.stats.host_skipped_steps[g] += 1
                continue                     # slow host: decode not done yet
            self._host_credit[g] -= 1.0
            with span("engine.decode") as sp:
                if sp is not None:
                    sp["rids"] = [self.slot_req[s].rid for s in active_g]
                next_tok, self._states[g] = self.backend.decode(
                    self.tokens[lo:hi], self._states[g])
            self.stats.host_decode_steps[g] += 1
            self.stats.host_active_slots[g] += len(active_g)
            with span("engine.retire") as sp:
                for s in active_g:
                    self.tokens[s, 0] = next_tok[s - lo]
                    req = self.slot_req[s]
                    req.out_tokens.append(int(next_tok[s - lo]))
                    self._note_token(req, now)
                    t = self.slot_thread[s]
                    t.remaining -= 1.0
                    if len(req.out_tokens) >= req.max_new_tokens:
                        if sp is not None:
                            sp.setdefault("rids", []).append(req.rid)
                        self._evict(s, now)
                    elif (req.next_call < len(req.tool_calls)
                          and len(req.out_tokens)
                          >= req.tool_calls[req.next_call][0]):
                        self._tool_call(s, now)
                    else:
                        self._maybe_demote(req, t)
        return len(active)

    def _sched_mark(self) -> tuple:
        """What a schedule span's ``info`` is measured against."""
        s = self.sched.stats
        return (s.steals, s.steal_attempts, s.rebalance_moves,
                set(self._kv_park))

    def _sched_note(self, info: dict, mark: tuple) -> None:
        """A schedule span's ``info``: the scheduler's steals, steal
        attempts and rebalance moves in it, and the requests it parked
        (preempted or regenerated)."""
        s = self.sched.stats
        info["steals"] = s.steals - mark[0]
        info["steal_attempts"] = s.steal_attempts - mark[1]
        info["rebalance_moves"] = s.rebalance_moves - mark[2]
        parked = [rid for rid in self._kv_park if rid not in mark[3]]
        if parked:
            info["parked"] = parked

    def _drained(self) -> bool:
        return (not any(self.slot_req) and not self._pending
                and not self._sleeping and not self._thinking
                and self.sched.queues.total_tasks() == 0
                and not any(st > 0 for st in self._stall))

    def run(self, max_steps: int = 1000) -> list[Request]:
        for _ in range(max_steps):
            self.step()
            if self._drained():
                break
        return self.completed

    # -- regeneration (backpressure / straggling client) ------------------------
    def regenerate_gang(self, gang: str) -> int:
        """Pull a gang's requests out of the slots — parking each slot's KV
        state and last token so the later re-admission resumes the
        continuation via the batched splice — and re-queue the closed
        bubble (affinity preserved).

        The old engine left the freed slots' tokens and the popped threads'
        running state behind: a re-queued gang decoded from stale tokens
        and could never be woken again once finished."""
        b = self._gangs.get(f"gang:{gang}")
        if b is None:
            return 0
        now = float(self.steps)
        # Members freed below go back onto a list *before* the bubble is
        # regenerated.  If the gang bubble is still a burst husk the
        # regeneration collects them (queued children are folded back in);
        # but a closed bubble that a rebalance has *expanded* is itself on
        # no queue and regenerate() is a no-op for it — releasing a member
        # into thin air would lose the request forever (found by the HBM
        # admit/park/steal property test).
        fold = b.home_list if b.home_list is not None \
            else self.sched.queues.global_queue()
        # a member claimed into _pending (waiting out its steal stall) keeps
        # its claim, whose migration is already paid: a re-claim would
        # steal and stall again, and a gang regenerated more often than one
        # stall would never get a slot.  It leaves the bubble, so the
        # regenerated gang cannot schedule it twice.
        for t in self._pending.values():
            if t.parent is b:
                b.children.remove(t)
                t.parent = None
        n = 0
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is not None and req.gang == gang and not req.done:
                t = self.slot_thread.pop(s)
                self.slot_req[s] = None
                if s in self._thinking:
                    # a hold-mode member mid-think: its KV is already
                    # extracted into the thinking entry — converting it to
                    # a ledger sleep (slot freed, wake deadline kept) is
                    # the only move that neither double-extracts nor
                    # collapses the pending tool response
                    e = self._thinking.pop(s)
                    self.tokens[s, 0] = 0
                    self._refund(s)
                    self.runtime.release(s, t, False, now)
                    if t.parent is not None:
                        t.parent.children.remove(t)
                        t.parent = None
                    self._sleeping.add(e)
                    self.stats.sleeps += 1
                    n += 1
                    continue
                g = self._group_of[s]
                with span("engine.extract") as sp:
                    if sp is not None:
                        sp["rid"] = req.rid
                    handle = self.backend.extract(
                        self._states[g], s - self._exec_groups[g][0])
                self._kv_park[req.rid] = (handle, int(self.tokens[s, 0]))
                self.stats.kv_parks += 1
                self.tokens[s, 0] = 0
                self._refund(s)   # parked KV lives host-side, off the budget
                self.runtime.release(s, t, False, now)
                fold.push(t)
                n += 1
        self.sched.regenerate(b, running={})
        return n

    # -- agentic sessions: tool-call sleep / wake ------------------------------
    def _tool_call(self, slot: int, now: float) -> None:
        """The resident request just hit its next tool-call marker: block
        it on the external event — sleep-and-release or hold-the-slot,
        per the engine's ``agentic_sleep`` knob."""
        req = self.slot_req[slot]
        _, think = req.tool_calls[req.next_call]
        req.next_call += 1
        wake_at = None if think is None else int(now) + int(think)
        if self.agentic_sleep:
            self._sleep_slot(slot, wake_at, now)
        else:
            self._hold_slot(slot, wake_at, now)

    def _sleep_slot(self, slot: int, wake_at: Optional[int], now: float
                    ) -> None:
        """Park the slot's KV and free it: the session's thread leaves
        every run queue (held in the SleepingLedger — a sleeping session
        is not schedulable work) and, unless ``sleep_retain_hbm``, its
        HBM reservation is refunded.  The freed slot admits someone else
        in the next wave: under load, this is where the capacity headroom
        comes from."""
        req = self.slot_req[slot]
        t = self.slot_thread.pop(slot)
        self.slot_req[slot] = None
        g = self._group_of[slot]
        with span("engine.extract") as sp:
            if sp is not None:
                sp["rid"] = req.rid
            handle = self.backend.extract(self._states[g],
                                          slot - self._exec_groups[g][0])
        entry = SleepEntry(req.rid, t, handle, int(self.tokens[slot, 0]),
                           self.topo.cpus[slot].path()[self._page_idx],
                           int(now), wake_at)
        self.tokens[slot, 0] = 0
        if self.sleep_retain_hbm and self._slot_charged[slot]:
            # keep the bytes reserved in the home group for the wake, but
            # detach them from the slot (someone else's claim will charge
            # it normally); released when the entry leaves the ledger
            entry.retained = self._page_of[slot]
            self._slot_charged[slot] = False
        else:
            self._refund(slot)
        self.runtime.release(slot, t, False, now)
        # detach a gang member from its bubble: a later burst of the
        # (regenerated) gang would otherwise re-push the sleeping thread
        # onto a run queue and double-schedule it on wake
        if t.parent is not None:
            t.parent.children.remove(t)
            t.parent = None
        self._sleeping.add(entry)
        self.stats.sleeps += 1
        self.stats.kv_parks += 1

    def _hold_slot(self, slot: int, wake_at: Optional[int], now: float
                   ) -> None:
        """The baseline: keep the slot (and its HBM reservation) through
        the think gap.  The KV is still extracted — the whole-host-batch
        decode advances every resident state, so a thinking slot's state
        must sit out host-side and be spliced back on wake or the
        continuation would be corrupted — but the slot admits nobody."""
        req = self.slot_req[slot]
        g = self._group_of[slot]
        handle = self.backend.extract(self._states[g],
                                      slot - self._exec_groups[g][0])
        self._thinking[slot] = SleepEntry(
            req.rid, self.slot_thread[slot], handle,
            int(self.tokens[slot, 0]),
            self.topo.cpus[slot].path()[self._page_idx], int(now), wake_at)
        self.tokens[slot, 0] = 0
        self.stats.holds += 1

    def _process_wakes(self, now: float) -> None:
        """Deliver scheduled tool responses: splice thinking slots back in
        place (hold mode), wake due ledger entries onto run queues (sleep
        mode), then drop the KV of sessions sleeping past the TTL."""
        for slot in sorted(self._thinking):
            e = self._thinking[slot]
            if e.wake_at is not None and e.wake_at <= now:
                self._wake_hold(slot, now)
        for e in self._sleeping.due(now):
            self._wake_entry(e, now)
        if self.session_ttl is not None:
            for e in self._sleeping.stale(now, self.session_ttl):
                self._evict_stale(e)

    def _wake_hold(self, slot: int, now: float) -> None:
        """Hold-mode wake: splice the held state back into the slot it
        never gave up."""
        e = self._thinking.pop(slot)
        req = self.slot_req[slot]
        g = self._group_of[slot]
        with span("engine.splice") as sp:
            if sp is not None:
                sp.update(rids=[req.rid], n=1)
            self._states[g] = self.backend.splice(
                self._states[g], [(slot - self._exec_groups[g][0], e.state)])
        self.stats.kv_splices += 1
        self.stats.kv_spliced_slots += 1
        self.tokens[slot, 0] = e.token
        req.wake_step = int(now)
        req.service_break = True
        self.stats.wakes += 1

    def _queue_wait_quote(self, page_comp, depth: int) -> float:
        """Expected wait (engine steps) before a page group can serve one
        more request: its queued backlog spread over its slots, plus —
        when the group is at its HBM budget — the time until residents
        free one reservation on their own (``_split_wait_quote``)."""
        w = depth / max(sum(1 for _ in page_comp.leaves()), 1)
        if self.hbm_budget is not None:
            need = self.kv_bytes - self._headroom(page_comp.index)
            if need > 1e-9:
                w += self._split_wait_quote(page_comp, need)
        return w

    def _wake_dest(self, entry: SleepEntry):
        """The wake-affinity quote: where should this session resume?

        Home is free (the KV handle splices back as a metadata edit on
        the paged backend); any other page group pays the believed
        transfer toll (``cost_model.rebalance_move_cost`` over the
        boundary crossed, byte-priced under a bandwidth table) on top of
        its queue/HBM wait.  The cheapest total wins, ties to home — so
        an idle fleet always restores affinity, and only genuine pressure
        at home (backlog, a full budget) buys the away move.  A home
        group lost to ``kill_host`` quotes infinite and the live groups
        compete on their own merits."""
        pages = self.topo.components("page")
        if not self.wake_quote:
            return entry.home_page if any(
                p is entry.home_page for p in pages) else pages[0]
        depths = self._page_depths()
        cm = self.sched.cost_model
        ranked = sorted(zip(pages, depths),
                        key=lambda pd: pd[0] is not entry.home_page)
        best, best_q = None, None
        for comp, depth in ranked:          # home first: wins ties
            toll = 0.0 if comp is entry.home_page else \
                cm.rebalance_move_cost(
                    self.topo.crossing_between(entry.home_page, comp),
                    self.kv_bytes)
            q = self._queue_wait_quote(comp, depth) + toll
            if best_q is None or q < best_q - 1e-9:
                best, best_q = comp, q
        return best

    def _wake_entry(self, e: SleepEntry, now: float) -> None:
        """Sleep-mode wake: the tool response landed.  Rebuild the
        continuation if the KV was stale-evicted (full-history re-prefill,
        billed at ``reprefill_unit`` per token like a kill_host orphan),
        park it for the admission splice, and push the thread where the
        wake-affinity quote says — an away move is billed at bill-model
        prices as an admission stall and flags the thread ``stolen`` so
        next-touch re-homes the session's KV data object."""
        req = self._reqs[e.rid]
        t = e.thread
        self._sleeping.pop(e.rid)
        if e.retained is not None:
            self.hbm_used[e.retained] -= self.kv_bytes
            e.retained = None
        if e.state is None:
            m = len(req.out_tokens)
            hist = req.prompt if m == 1 else np.concatenate(
                [req.prompt, np.asarray(req.out_tokens[:-1], np.int32)])
            with span("engine.prefill") as sp:
                if sp is not None:
                    sp.update(rids=[req.rid], n=1, length=len(hist))
                _, st = self.backend.prefill(hist)
            tok = int(req.out_tokens[-1])
            debt = (len(req.prompt) + m - 1) * self.reprefill_unit
            if debt:
                self._restore_debt[req.rid] = \
                    self._restore_debt.get(req.rid, 0.0) + debt
            self.stats.wake_reprefills += 1
        else:
            st, tok = e.state, e.token
        self._kv_park[req.rid] = (st, tok)
        dest = self._wake_dest(e)
        if dest is e.home_page:
            self.stats.wake_home += 1
        else:
            self.stats.wake_away += 1
            bill = self.sched.bill_model.rebalance_move_cost(
                self.topo.crossing_between(e.home_page, dest),
                self.kv_bytes)
            if bill:
                self._restore_debt[req.rid] = \
                    self._restore_debt.get(req.rid, 0.0) + bill
            t.stolen = True          # next touch re-homes the KV data id
        self.sched.queues.queue_of(dest).push(t)
        req.wake_step = int(now)
        self.stats.wakes += 1

    def _evict_stale(self, e: SleepEntry) -> None:
        """Drop a sleeping session's parked KV (its pages go back to the
        pool on a paged backend); the entry survives — a later wake
        re-prefills the continuation from the token history."""
        drop = getattr(self.backend, "drop", None)
        if drop is not None:
            drop(e.state)
        e.state = None
        if e.retained is not None:
            self.hbm_used[e.retained] -= self.kv_bytes
            e.retained = None
        self.stats.stale_evictions += 1

    def wake(self, rid: int) -> bool:
        """Deliver a tool response from the client side: wake session
        ``rid`` now.  Markers submitted with ``think_steps=None`` wait
        for exactly this call (``run()`` alone will not drain them);
        scheduled markers wake themselves and need it only to wake
        *early*.  Returns False when ``rid`` is not asleep."""
        now = float(self.steps)
        e = self._sleeping.get(rid)
        if e is not None:
            self._wake_entry(e, now)
            return True
        for slot, e in list(self._thinking.items()):
            if e.rid == rid:
                self._wake_hold(slot, now)
                return True
        return False

    # -- elastic fleet: live host loss / join ---------------------------------
    def _maybe_snapshot_kv(self, step: int) -> None:
        """On the store's cadence, snapshot every resident continuation:
        (backend state via the non-mutating ``peek``, last emitted token,
        tokens emitted so far) per live request.  Parked continuations are
        already host-side and need no snapshot."""
        if not self.kv_store.due(step):
            return
        entries: dict[int, tuple] = {}
        for s in range(self.n_slots):
            req = self.slot_req[s]
            if req is None or req.done or not req.out_tokens:
                continue
            g = self._group_of[s]
            st = self.backend.peek(self._states[g],
                                   s - self._exec_groups[g][0])
            entries[req.rid] = (st, int(self.tokens[s, 0]),
                                len(req.out_tokens))
        self.kv_store.maybe_snapshot(step, entries)

    def _buy_redeal(self, slot: int, now: float) -> None:
        """Commit one machine-wide re-spread and land its bill exactly the
        way :meth:`_maybe_rebalance` does: the flat trigger-side cost
        stalls the triggering slot, the level-table ingest tolls stall the
        receiving groups' slots, and the steal-spend window resets."""
        self.runtime.rebalance(slot, now, level="page")
        cost = self.policy.consume_cost()
        if cost:
            self._stall[slot] += cost
            self.stats.stall_steps += cost
        for comp_name, extra in self.sched.stats.last_rebalance_ingest.items():
            for leaf in self.topo.component(comp_name).leaves():
                self._stall[leaf.cpu] += extra
                self.stats.stall_steps += extra
        self.stats.rebalances += 1
        self._paid.clear()
        self._cost_mark = self.sched.stats.steal_cost
        self._steps_since_rebalance = 0

    def kill_host(self, name: str, *, restart: bool = False) -> dict:
        """Remove host ``name`` mid-flight — the elastic failure path.

        The dead host's slots leave the hierarchy (fresh ``KeyError`` for
        stale handles, cpu ids never renumber), its residents' KV
        reservations vanish from the HBM ledger (the pages died with the
        host — no extract), queued work homed anywhere in its subtree
        folds one level up onto the surviving parent list (the paper's
        §3.3.3 regeneration move, affinity kept as wide as the loss
        allows), and every orphaned request is re-parked as a
        continuation: restored from the newest ``kv_store`` snapshot (a
        ``kv_restore_level`` boundary toll on its KV bytes plus a
        teacher-forced replay of the tokens emitted since, at
        ``reprefill_unit`` steps/token) or re-prefilled from its whole
        history — whichever the cost model quotes cheaper.  The quote is
        billed as an admission stall when the orphan re-enters a
        surviving slot, and the exact rebalance quote then re-deals the
        survivor fleet.  Parked continuations (``_kv_park``) survive: they
        live host-side, not in the dead host's HBM.

        ``restart=True`` models the drain-and-restart operator instead —
        the baseline ``serve/host_loss_goodput`` gates against: the whole
        job restarts on the survivor mesh, so every in-flight request
        *fleet-wide* is torn down and re-prefilled from scratch, snapshots
        unused.

        Returns a summary dict (orphan count, restore/re-prefill split,
        re-deal quote).  Streams are unaffected: a restored or
        re-prefilled orphan continues token-for-token where it left off
        (teacher forcing — property-tested).
        """
        assert self.mode == "runtime", "kill_host needs the runtime engine"
        assert self._host_idx is not None, \
            "single-host topology has no host level to kill"
        assert self.per_host_decode, "kill_host needs per-host execution"
        host = self.topo.component(name)
        assert host.level.name == "host", f"{name!r} is not a host"
        assert any(h is not host for h in self.topo.components("host")), \
            "cannot kill the last host"
        now = float(self.steps)
        dead = {leaf.cpu for leaf in host.leaves()}
        fold = self.sched.queues.queue_of(host.parent)
        gq = self.sched.queues.global_queue()
        snaps = {} if (restart or self.kv_store is None) \
            else self.kv_store.restore()

        # 1. claims pending on doomed slots dissolve: the thread was never
        #    spliced in, so it simply returns to a surviving list (its
        #    parked KV, if any, is host-side and intact)
        requeued = 0
        for s in list(self._pending):
            if restart or s in dead:
                t = self._pending.pop(s)
                self._refund(s)
                self.runtime.release(s, t, False, now)
                (gq if restart else fold).push(t)
                requeued += 1

        # 2. residents of doomed slots are orphans: pop the thread, free
        #    the slot — their KV is gone, restoration is decided below.
        #    A thinking (hold-mode) resident's held handle counts as died
        #    with its host too: drop it (freeing pool pages if the shard
        #    survives a restart teardown) and let the orphan path rebuild
        #    the continuation from history like any other resident.
        drop = getattr(self.backend, "drop", None)
        for s in list(self._thinking):
            if restart or s in dead:
                e = self._thinking.pop(s)
                if drop is not None and s not in dead:
                    drop(e.state)
        orphans: list[tuple] = []
        doomed = range(self.n_slots) if restart else sorted(dead)
        for s in doomed:
            if s in self._dead_slots:
                continue
            self._stall[s] = 0.0
            req = self.slot_req[s]
            if req is None or req.done:
                continue
            t = self.slot_thread.pop(s)
            self.slot_req[s] = None
            self.tokens[s, 0] = 0
            self._refund(s)
            self.runtime.release(s, t, False, now)
            orphans.append((req, t))

        # 3. queued tasks homed in the dead subtree move one level up;
        #    bubbles whose regeneration home died re-home the same way
        moved_q = 0
        dead_comps, stack = [], [host]
        while stack:
            c = stack.pop()
            dead_comps.append(c)
            stack.extend(c.children)
        dead_ids = {id(c) for c in dead_comps}
        for c in dead_comps:
            q = self.sched.queues.queue_of(c)
            for task in list(q.tasks):
                q.remove(task)
                fold.push(task)
                moved_q += 1
        for b in self._gangs.values():
            if b.home_list is not None and id(b.home_list.comp) in dead_ids:
                b.home_list = fold

        # 4. topology surgery + derived-cache rebuild
        self.topo.remove_component(name)
        self.sched.queues.sync()
        self._queues_by_name = None          # _home_queue rebuilds lazily
        self._page_host = [p.path()[self._host_idx]
                           for p in self.topo.components("page")]
        self._dead_slots |= dead
        self._speed_by_host.pop(id(host), None)
        self._host_group.pop(id(host), None)

        # 5. restore-vs-reprefill: both paths produce the exact
        #    continuation (state, last token) into _kv_park; the quoted
        #    cost is billed at the orphan's re-admission
        bm = self.sched.bill_model
        restored = reprefilled = 0
        for req, t in orphans:
            m = len(req.out_tokens)
            assert m >= 1, "a resident request always holds >=1 token"
            reprefill_q = (len(req.prompt) + m - 1) * self.reprefill_unit
            snap = snaps.get(req.rid)
            usable = (snap is not None and 1 <= snap.emitted <= m
                      and (snap.emitted == m
                           or hasattr(self.backend, "replay")))
            restore_q = (bm.rebalance_move_cost(self.kv_restore_level,
                                                self.kv_bytes)
                         + (m - snap.emitted) * self.reprefill_unit) \
                if usable else float("inf")
            if restore_q < reprefill_q:
                assert int(snap.tok) == int(req.out_tokens[snap.emitted - 1])
                st = snap.state if snap.emitted == m else self.backend.replay(
                    snap.state, req.out_tokens[snap.emitted - 1:m - 1])
                debt = restore_q
                restored += 1
                self.stats.kv_restores += 1
            else:
                hist = req.prompt if m == 1 else np.concatenate(
                    [req.prompt, np.asarray(req.out_tokens[:-1], np.int32)])
                _, st = self.backend.prefill(hist)
                debt = reprefill_q
                reprefilled += 1
                self.stats.reprefills += 1
            self._kv_park[req.rid] = (st, int(req.out_tokens[-1]))
            self.stats.kv_parks += 1
            self._restore_debt[req.rid] = debt
            (gq if restart else fold).push(t)

        # 6. the exact rebalance quote re-deals the survivor fleet, billed
        #    from the first surviving slot (the fleet just changed shape —
        #    the skew trigger's window is stale by construction)
        movable, est = self.sched.estimate_rebalance("page", None)
        if movable >= 1:
            self._buy_redeal(next(self.topo.root.leaves()).cpu, now)
        self.stats.host_kills += 1
        self.stats.orphaned += len(orphans)
        return {"host": name, "orphaned": len(orphans),
                "restored": restored, "reprefilled": reprefilled,
                "requeued_pending": requeued, "queued_moved": moved_q,
                "redeal": movable >= 1, "redeal_quote": round(est, 4)}

    def join_host(self, name: Optional[str] = None, *,
                  slots: Optional[int] = None, speed: float = 1.0,
                  proactive: bool = True) -> str:
        """Grow the fleet by one host live — scale-out under load.

        The new host's slots join the hierarchy with fresh cpu ids, a
        fresh backend shard, zeroed HBM ledger entries per new page group,
        and its own decode-speed credit (``speed`` < 1 models a slow
        joiner exactly like ``host_speed``).  With ``proactive`` the
        engine quotes one machine-wide re-spread onto the new capacity
        against the expected cost of the joiner pulling its fair share
        one costed steal at a time (each dragging KV across the host
        boundary), and buys the deal only when the quote beats staying
        put — an unjustified joiner serves newly submitted work instead.
        ``name``, when given, must equal the name the topology assigns
        (names are monotone — a dead host's name is never reused).
        Returns the new host's name."""
        assert self.mode == "runtime", "join_host needs the runtime engine"
        assert self._host_idx is not None, \
            "single-host topology has no host level to grow"
        assert self.per_host_decode, "join_host needs per-host execution"
        assert 0.0 < speed <= 1.0, speed
        now = float(self.steps)
        n_new = int(slots) if slots is not None else \
            max(len(list(h.leaves())) for h in self.topo.components("host"))
        groups = max(-(-n_new // self._group), 1)
        b, r = divmod(n_new, groups)
        page_sizes = [b + 1] * r + [b] * (groups - r)
        host = self.topo.add_component("host", (groups, _fanout(page_sizes)))
        if name is not None:
            assert name == host.name, \
                f"topology assigned {host.name!r}, caller expected {name!r}"
        self.sched.queues.sync()
        self._queues_by_name = None
        lo = self.n_slots
        new_cpus = [leaf.cpu for leaf in host.leaves()]
        assert new_cpus == list(range(lo, lo + n_new)), new_cpus
        self.n_slots += n_new
        self._page_of.extend(self.topo.cpus[s].path()[self._page_idx].index
                             for s in new_cpus)
        max_page = max(p.index for p in self.topo.components("page"))
        self.hbm_used.extend(
            0.0 for _ in range(max_page + 1 - len(self.hbm_used)))
        self._page_host = [p.path()[self._host_idx]
                           for p in self.topo.components("page")]
        self._slot_charged.extend([False] * n_new)
        self._stall.extend([0.0] * n_new)
        self.slot_req.extend([None] * n_new)
        g_new = len(self._exec_groups)
        self._exec_groups.append((lo, lo + n_new))
        self._group_of.extend([g_new] * n_new)
        self._group_speed.append(float(speed))
        self._host_credit.append(0.0)
        self._host_group[id(host)] = g_new
        if self._speed_by_host or speed < 1.0:
            # keep the speed ruler total: hosts the engine never priced
            # run nominal.  (The scheduler only *consults* the ruler when
            # the engine was built speed_aware with host_speed; a slow
            # joiner on a speed-blind engine still executes slow — the
            # credit accumulator above — it is just not steered around.)
            for h in self.topo.components("host"):
                self._speed_by_host.setdefault(id(h), 1.0)
            self._speed_by_host[id(host)] = float(speed)
        st, tok = self.backend.init(n_new)
        self._states.append(st)
        self.tokens = np.concatenate([self.tokens, tok], axis=0)
        self.stats.host_decode_steps.append(0)
        self.stats.host_active_slots.append(0)
        self.stats.host_skipped_steps.append(0)
        self.stats.host_joins += 1
        if proactive:
            movable, est = self.sched.estimate_rebalance("page", None)
            if movable >= 1:
                # the steal path the deal replaces: the joiner pulls its
                # fair share of the backlog one costed host-crossing
                # steal at a time, each dragging one request's KV
                cm = self.sched.cost_model
                share = movable * n_new / max(len(self.topo.live_cpus()), 1)
                src = next((p for p in self.topo.components("page")
                            if self.topo.ancestor_at(p, "host") is not host),
                           None)
                per_steal = cm.steal_cost(
                    self.topo.levels_crossed(lo, src), 1, "host",
                    self.kv_bytes) if src is not None else 0.0
                if est < share * per_steal:
                    self._buy_redeal(lo, now)
        return host.name

    # -- introspection ---------------------------------------------------------
    def counters(self) -> dict:
        """Engine + scheduler ledger in one dict (benchmark rows)."""
        s = self.sched.stats
        out = {
            "steps": self.steps,
            "steals": s.steals, "steal_attempts": s.steal_attempts,
            "steal_refusals": s.steal_refusals,
            "steal_cost": round(s.steal_cost, 4),
            "rebalances": s.rebalances,
            "rebalance_moves": s.rebalance_moves,
            "data_migrations": self.runtime.data_migrations,
            "kv_migrations": self.stats.kv_migrations,
            "kv_page_moves": self.stats.kv_page_moves,
            "kv_host_moves": self.stats.kv_host_moves,
            "kv_splices": self.stats.kv_splices,
            "kv_spliced_slots": self.stats.kv_spliced_slots,
            "kv_parks": self.stats.kv_parks,
            "prefills": self.stats.prefills,
            "prefill_waves": self.stats.prefill_waves,
            "local_rebalances": self.stats.local_rebalances,
            "stall_steps": round(self.stats.stall_steps, 4),
            "hbm_slot_waits": self.stats.hbm_slot_waits,
            "hbm_refusals": self.stats.hbm_refusals,
            "gang_splits": self.stats.gang_splits,
            "gang_split_members": self.stats.gang_split_members,
            "preemptions": self.stats.preemptions,
            "preempt_parks": self.stats.preempt_parks,
            "demotions": self.stats.demotions,
            "host_decode_steps": list(self.stats.host_decode_steps),
            "host_active_slots": list(self.stats.host_active_slots),
            "host_skipped_steps": list(self.stats.host_skipped_steps),
            # effective per-host throughput: decoded slot-tokens per
            # engine step — what a straggler actually delivers
            "host_throughput": [
                round(a / max(self.steps, 1), 4)
                for a in self.stats.host_active_slots],
        }
        if self.stats.host_kills or self.stats.host_joins:
            # elastic ledger: keyed only when the fleet actually changed
            # shape, so every pre-elastic benchmark row stays bit-identical
            out.update({
                "host_kills": self.stats.host_kills,
                "host_joins": self.stats.host_joins,
                "orphaned": self.stats.orphaned,
                "kv_restores": self.stats.kv_restores,
                "reprefills": self.stats.reprefills,
            })
        if self.stats.sleeps or self.stats.holds:
            # agentic ledger: keyed only when a tool call actually fired,
            # so every pre-agentic benchmark row stays bit-identical
            out.update({
                "sleeps": self.stats.sleeps,
                "holds": self.stats.holds,
                "hold_slot_steps": self.stats.hold_slot_steps,
                "wakes": self.stats.wakes,
                "wake_home": self.stats.wake_home,
                "wake_away": self.stats.wake_away,
                "wake_reprefills": self.stats.wake_reprefills,
                "stale_evictions": self.stats.stale_evictions,
            })
        return out
