"""Posterior-predictive serving engine: continuous batching over a fixed
slot axis, Bayesian model averaging over K ensemble members, live snapshot
refresh from the coupled sampler.

The structural invariant (pinned by ``tests/test_serve_engine.py``): the
decode hot path is ONE compiled program.  Its signature is
``(members (K,...), pooled caches (K, S, ...), tokens (S,1), done (S,),
budget (S,), key)`` — every quantity that changes as requests join, finish,
or the ensemble refreshes is *data* (masks, slot-indexed writes, swapped
member pytrees of identical shape), never a shape.  Admission compiles once
per distinct prompt length (prefill is length-shaped by nature; bucket
prompts upstream if that matters), and writes the new request's K member
caches into its slot with a traced slot index.

Per-slot decode runs as ``vmap(member) ∘ vmap(slot)`` over the model's
single-stream ``decode_step``, which gives every slot its own cache time
pointer ``t`` — the property continuous batching needs and the batched
legacy path lacked (one scalar ``t`` for the whole batch).  Done/free slots
keep computing (fixed-shape batching burns their FLOPs regardless); their
emissions are masked to ``pad_id`` and their cache writes land in slots
whose validity masks hide them from any later request (positions are
rewritten by the next prefill before they become attendable).

``paged=True`` swaps the dense per-slot stripes for the block-paged pool
(DESIGN.md §8): same invariant, but block tables and context lengths are
extra DATA arguments to the decode program, admission additionally gates on
the page allocator's worst-case reservation, and done-slot writes are
redirected in-program to the reserved sink page so recycled pages can never
be corrupted mid-batch.  ``fused_select`` routes the mixture + selection
through one Pallas kernel (token draws bit-identical via Gumbel-argmax).

The scheduler clock, admission policy and latency accounting live in
``scheduler.py``; member health gating and live refresh in ``registry.py``;
see DESIGN.md §5 for the full contract.
"""
from __future__ import annotations

import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.sampling import GREEDY, SamplingParams, select_tokens

from .bma import BMA_MODES, fused_mixture_select, mixture_logprobs
from .cache_pool import CachePool, PagedCachePool
from .registry import ChainRefresher, SnapshotRegistry
from .scheduler import FCFSQueue, Request, RequestResult


@dataclass
class _Active:
    result: RequestResult
    submit_s: float
    tokens: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)


@dataclass
class ServeReport:
    """Aggregate outcome of one ``ServeEngine.run``: per-request results +
    the latency/throughput numbers the serving benchmark records."""

    results: list
    wall_s: float
    decode_steps: int
    total_tokens: int
    trace_counts: dict
    pool: dict
    registry: dict
    refresher: dict | None

    @property
    def tokens_per_s(self) -> float:
        return self.total_tokens / max(self.wall_s, 1e-12)

    def latency_percentiles(self) -> dict:
        """p50/p99 of request completion latency and first-token latency
        (seconds, queueing included; a first token counts once the host
        holds it, so the prefill's device time is inside)."""
        lat = np.asarray([r.latency_s for r in self.results], np.float64)
        ftl = np.asarray([r.first_token_ready_s for r in self.results], np.float64)
        pct = lambda a, q: float(np.percentile(a, q)) if a.size else float("nan")
        return {
            "latency_p50_s": pct(lat, 50),
            "latency_p99_s": pct(lat, 99),
            "first_token_p50_s": pct(ftl, 50),
            "first_token_p99_s": pct(ftl, 99),
        }


class ServeEngine:
    """Continuous-batching BMA decode over a pooled slot axis.

    ``members``: a (K, ...)-stacked parameter pytree or a
    :class:`SnapshotRegistry` (live refresh).  ``refresher`` (optional, a
    :class:`ChainRefresher` or overlapped
    :class:`~repro.serve.engine.refresh.RefreshScheduler` feeding the same
    registry) is bound at construction and pumped EVERY decode tick; it
    amortizes one sampler chunk per ``refresh_every`` ticks — stale members
    serve until the registry promotes a candidate that passes the spread
    gate."""

    def __init__(
        self,
        cfg,
        model,
        members,
        *,
        num_slots: int,
        max_seq: int,
        sampling: SamplingParams = GREEDY,
        bma: str = "probs",
        eos_id: int | None = None,
        pad_id: int = 0,
        cache_dtype=None,
        refresher: ChainRefresher | None = None,
        refresh_every: int = 0,
        compress_parked: bool = False,
        record_logprobs: bool = False,
        seed: int = 0,
        mesh=None,
        member_axis: str = "member",
        slot_axis: str = "slot",
        paged: bool = False,
        block_size: int = 16,
        num_blocks: int | None = None,
        prefix_sharing: bool = True,
        fused_select: bool | None = None,
    ):
        if bma not in BMA_MODES:
            raise ValueError(f"bma must be one of {BMA_MODES}")
        self.cfg, self.model = cfg, model
        self.registry = members if isinstance(members, SnapshotRegistry) else SnapshotRegistry(members)
        self.sampling = sampling
        self.bma = bma
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        self.max_seq = int(max_seq)
        self.cache_dtype = cache_dtype
        self.refresher = refresher
        self.refresh_every = int(refresh_every)
        if refresher is not None and refresher.registry is not self.registry:
            raise ValueError("refresher must feed this engine's registry")
        self._seen_version = self.registry.version
        self.record_logprobs = bool(record_logprobs)
        self.paged = bool(paged)
        # Fused mixture+selection kernel: on by default where it compiles to
        # a real kernel (TPU); the unfused jnp path stays the default on CPU
        # so interpret-mode overhead never taxes the test/bench hot loop.
        # Either way the numerics are pinned equal by tests/test_paged_attention.py.
        self._fused_select = (
            jax.default_backend() == "tpu" if fused_select is None else bool(fused_select)
        )
        if self.paged:
            self.pool = PagedCachePool(
                cfg,
                model,
                num_members=self.registry.num_members,
                num_slots=num_slots,
                max_seq=max_seq,
                block_size=block_size,
                num_blocks=num_blocks,
                dtype=cache_dtype or cfg.compute_dtype,
                compress_parked=compress_parked,
                prefix_sharing=prefix_sharing,
            )
        else:
            self.pool = CachePool(
                cfg,
                model,
                num_members=self.registry.num_members,
                num_slots=num_slots,
                max_seq=max_seq,
                dtype=cache_dtype or cfg.compute_dtype,
                compress_parked=compress_parked,
            )
        S = self.pool.num_slots
        self._tokens = jnp.full((S, 1), self.pad_id, jnp.int32)
        self._done = jnp.ones((S,), bool)
        self._budget = jnp.zeros((S,), jnp.int32)
        base = jax.random.PRNGKey(seed)
        self._key_decode = jax.random.fold_in(base, 0)
        self._key_admit = jax.random.fold_in(base, 1)
        self.trace_counts: Counter = Counter()
        self.decode_steps = 0
        self.page_waits = 0  # ticks an FCFS head waited for pages, all runs
        # Multi-device layout (DESIGN.md §7): pooled caches (K, S, ...) shard
        # member/slot over their two leading dims, slot-state arrays shard
        # over slot, members over member; any dim a mesh axis does not divide
        # evenly replicates.  Every sharding is pinned explicitly on BOTH
        # sides of the two jitted programs so the donated-buffer feedback
        # loop (decode output -> next decode input) has fixed-point layouts —
        # that is what preserves the one-compiled-decode-program invariant
        # under a mesh.
        self.mesh = mesh
        self._member_axis, self._slot_axis = member_axis, slot_axis
        self._placed_version: int | None = None
        # the unsharded "home" of the member stack — where a pre-staged
        # candidate must land for promotion to be a pure pointer flip
        leaf = jax.tree.leaves(self.registry.members)[0]
        devs = leaf.devices() if hasattr(leaf, "devices") else set()
        self._home_device = next(iter(devs)) if len(devs) == 1 else None
        if mesh is None and self._home_device is not None:
            # Commit every buffer feeding the compiled programs NOW.  A
            # promoted candidate arrives COMMITTED (device_put at the flip),
            # and committed vs uncommitted arguments are different lowerings
            # even under one trace — left uncommitted, the first
            # post-promotion decode and admit each silently re-lower and
            # re-run XLA (~0.6s stalls on the serving path: exactly the
            # bimodal p99 this engine exists to avoid).  Committing up front
            # puts every program in the committed fixed point from the
            # first trace; the mesh path gets the same effect from its
            # pinned in/out shardings.
            put = lambda t: jax.device_put(t, self._home_device)
            self.registry.members = put(self.registry.members)
            self.pool.caches = put(self.pool.caches)
            self._tokens = put(self._tokens)
            self._done = put(self._done)
            self._budget = put(self._budget)
            self._placed_version = self.registry.version
        if mesh is None:
            # the two compiled entry points; caches are donated through both
            # so the pool's buffers are recycled in place, never copied
            if self.paged:
                self._decode = jax.jit(self._decode_paged_fn, donate_argnums=(1,))
                self._admit = jax.jit(self._admit_paged_fn, donate_argnums=(1,))
            else:
                self._decode = jax.jit(self._decode_fn, donate_argnums=(1,))
                self._admit = jax.jit(self._admit_fn, donate_argnums=(1,))
        else:
            from jax.sharding import NamedSharding, PartitionSpec

            from repro.distributed.sharding import leading_axes_shardings

            rep = NamedSharding(mesh, PartitionSpec())
            # Paged pools have no slot axis — pages are shared across slots —
            # so they shard over members only and the page pool replicates
            # along the slot mesh axis.  Dense pools shard (member, slot).
            cache_axes = (member_axis,) if self.paged else (member_axis, slot_axis)
            cache_s = leading_axes_shardings(self.pool.caches, cache_axes, mesh)
            mem_s = leading_axes_shardings(self.registry.members, (member_axis,), mesh)
            tok_s = leading_axes_shardings(self._tokens, (slot_axis,), mesh)
            slot_s = leading_axes_shardings(self._done, (slot_axis,), mesh)
            self._cache_shardings, self._member_shardings = cache_s, mem_s
            self.pool.caches = jax.device_put(self.pool.caches, cache_s)
            self._tokens = jax.device_put(self._tokens, tok_s)
            self._done = jax.device_put(self._done, slot_s)
            self._budget = jax.device_put(self._budget, slot_s)
            if self.paged:
                tab_s = leading_axes_shardings(
                    jnp.zeros((S, self.pool.alloc.blocks_per_slot), jnp.int32),
                    (slot_axis,),
                    mesh,
                )
                self._decode = jax.jit(
                    self._decode_paged_fn,
                    donate_argnums=(1,),
                    in_shardings=(mem_s, cache_s, tok_s, slot_s, slot_s, tab_s, slot_s, rep),
                    out_shardings=(slot_s, tok_s, cache_s, slot_s, slot_s, slot_s),
                )
                self._admit = jax.jit(
                    self._admit_paged_fn,
                    donate_argnums=(1,),
                    in_shardings=(mem_s, cache_s, tok_s, slot_s, slot_s, rep, rep, rep, rep, rep),
                    out_shardings=(cache_s, tok_s, slot_s, slot_s, rep, rep, rep),
                )
            else:
                self._decode = jax.jit(
                    self._decode_fn,
                    donate_argnums=(1,),
                    in_shardings=(mem_s, cache_s, tok_s, slot_s, slot_s, rep),
                    # (emit, feed, caches, done, budget, logp) — logp is (S, V),
                    # slot-leading like the masks
                    out_shardings=(slot_s, tok_s, cache_s, slot_s, slot_s, slot_s),
                )
                self._admit = jax.jit(
                    self._admit_fn,
                    donate_argnums=(1,),
                    in_shardings=(mem_s, cache_s, tok_s, slot_s, slot_s, rep, rep, rep, rep),
                    out_shardings=(cache_s, tok_s, slot_s, slot_s, rep, rep, rep),
                )
        if refresher is not None and hasattr(refresher, "bind"):
            # pacing, spare-device placement and warm-up compilation happen
            # here, at construction — never on a serving request
            refresher.bind(self)

    # -- compiled programs --------------------------------------------------

    def _members(self):
        """Registry members, placed on the mesh.  ``device_put`` with the
        member sharding is cached on ``registry.version`` so a live refresh
        re-places exactly once per promotion, not per decode tick (re-putting
        an already-placed tree is a no-op but still walks the pytree)."""
        if self.mesh is not None and self._placed_version != self.registry.version:
            self.registry.members = jax.device_put(
                self.registry.members, self._member_shardings
            )
            self._placed_version = self.registry.version
        elif self._home_device is not None and self._placed_version != self.registry.version:
            # unsharded: promotions from ANY source (overlapped scheduler,
            # sync ChainRefresher, manual propose) are re-committed to the
            # home device before decode consumes them, so the decode/admit
            # lowerings never see a committedness change (see __init__);
            # the overlapped flip pre-places and marks, making this a no-op
            self.registry.members = jax.device_put(
                self.registry.members, self._home_device
            )
            self._placed_version = self.registry.version
        return self.registry.members

    def _place_members(self, tree):
        """Pre-stage a candidate member stack with the engine's pinned
        placement — the mesh ``NamedSharding``s, or the unsharded home
        device — so a later promotion is a pure pointer flip that the
        compiled decode program cannot distinguish from the old buffers.
        The ``device_put`` is async-dispatched (no host sync)."""
        if self.mesh is not None:
            return jax.device_put(tree, self._member_shardings)
        if self._home_device is not None:
            return jax.device_put(tree, self._home_device)
        return tree

    def mark_members_placed(self) -> None:
        """Tell :meth:`_members` the current registry version is already in
        engine placement (the overlapped refresher pre-stages candidates
        through :meth:`_place_members`, so the per-promotion re-put would be
        redundant pytree work)."""
        self._placed_version = self.registry.version

    def _note_version(self) -> None:
        """Per-tick version watch: on a promotion, eagerly invalidate the
        paged pool's stale-version prefix entries (they can never be hit
        again — the sharing key includes the version)."""
        if self.registry.version != self._seen_version:
            self._seen_version = self.registry.version
            if self.paged:
                self.pool.invalidate_version(self.registry.version)

    @property
    def decode_trace_count(self) -> int:
        """How many times the decode program has been (re)traced — the
        continuous-batching acceptance pin asserts this stays at 1."""
        return self.trace_counts["decode"]

    def _eos_hits(self, tok):
        if self.eos_id is None:
            return jnp.zeros(tok.shape, bool)
        return tok == self.eos_id

    @jax.named_scope("serve.bma_select")
    def _mix_select(self, logits, key):
        """Per-tick BMA mixture + token selection over the slot axis:
        (K, S, V) member logits -> (tokens (S,), mixture logprobs (S, V)).
        Fused (one Pallas kernel) or unfused — same numerics, pinned by
        tests/test_paged_attention.py."""
        if self._fused_select:
            return fused_mixture_select(logits, key, mode=self.bma, sampling=self.sampling)
        logp = mixture_logprobs(logits, self.bma)
        return select_tokens(logp, key, self.sampling), logp

    def _select_tail(self, tok, logp, done, budget):
        """Shared emit/feed/done bookkeeping after token selection."""
        newly_done = (~done) & (self._eos_hits(tok) | (budget <= 1))
        emit = jnp.where(done, jnp.int32(self.pad_id), tok)
        next_done = done | newly_done
        feed = jnp.where(next_done, jnp.int32(self.pad_id), tok)[:, None]
        return emit, feed, next_done, budget - 1, logp

    @jax.named_scope("serve.decode")
    def _decode_fn(self, members, caches, tokens, done, budget, key):
        self.trace_counts["decode"] += 1  # trace-time side effect only

        def member_step(p, c):
            def slot_step(cs, tok):
                logits, new_cs = self.model.decode_step(self.cfg, p, cs, tok[None])
                return logits[0, 0], new_cs  # (V,), slot cache

            return jax.vmap(slot_step)(c, tokens)

        logits, new_caches = jax.vmap(member_step)(members, caches)  # (K, S, V)
        tok, logp = self._mix_select(logits, key)
        emit, feed, next_done, budget, logp = self._select_tail(tok, logp, done, budget)
        return emit, feed, new_caches, next_done, budget, logp

    @jax.named_scope("serve.decode")
    def _decode_paged_fn(self, members, pools, tokens, done, budget, tables, ctx, key):
        """Paged twin of :meth:`_decode_fn`.  Block tables (S, M) and context
        lengths (S,) are DATA — page churn never retraces.  The destination
        page for each slot's write is computed in-program, with done/free
        slots redirected to the sink page 0 so their garbage writes can never
        land in a page that was recycled to another request mid-batch."""
        self.trace_counts["decode"] += 1

        S, M = tables.shape
        j = jnp.clip(ctx // self.pool.block_size, 0, M - 1)
        write_block = jnp.where(done, 0, tables[jnp.arange(S), j])  # (S,)

        def member_step(p, pool):
            # on a mesh the pools are member-sharded: the kernel, a custom
            # call GSPMD cannot partition, would gather them whole
            return self.model.paged.decode_step(
                self.cfg, p, pool, tokens, tables, ctx, write_block,
                sharded=self.mesh is not None,
            )

        logits, new_pools = jax.vmap(member_step)(members, pools)  # (K, S, 1, V)
        tok, logp = self._mix_select(logits[:, :, 0], key)
        emit, feed, next_done, budget, logp = self._select_tail(tok, logp, done, budget)
        return emit, feed, new_pools, next_done, budget, logp

    @jax.named_scope("serve.admit")
    def _admit_fn(self, members, caches, tokens, done, budget, prompt, slot, max_new, key):
        self.trace_counts[f"admit_len{prompt.shape[-1]}"] += 1

        def member_prefill(p):
            return self.model.prefill(
                self.cfg, p, {"tokens": prompt}, self.max_seq, self.cache_dtype
            )

        logits, slot_cache = jax.vmap(member_prefill)(members)  # (K,1,1,V), (K,...)
        new_caches = jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_index_in_dim(
                full, one.astype(full.dtype), slot, 1
            ),
            caches,
            slot_cache,
        )
        logp = mixture_logprobs(logits[:, 0, -1], self.bma)  # (V,)
        tok = select_tokens(logp, key, self.sampling)  # scalar
        slot_done = self._eos_hits(tok) | (max_new <= 1)
        feed = jnp.where(slot_done, jnp.int32(self.pad_id), tok)
        tokens = tokens.at[slot, 0].set(feed)
        done = done.at[slot].set(slot_done)
        budget = budget.at[slot].set(max_new - 1)
        return new_caches, tokens, done, budget, tok, slot_done, logp

    @jax.named_scope("serve.admit")
    def _admit_paged_fn(self, members, pools, tokens, done, budget, prompt,
                        table_row, slot, max_new, key):
        """Paged twin of :meth:`_admit_fn`: dense prefill (length-shaped,
        same bucketing caveat) scattered into the slot's table-row pages.
        Shared prefix pages get rewritten with bit-identical KV (position-
        local), so concurrent sharers are unaffected."""
        self.trace_counts[f"admit_len{prompt.shape[-1]}"] += 1

        def member_prefill(p, pool):
            logits, slot_cache = self.model.prefill(
                self.cfg, p, {"tokens": prompt}, self.max_seq, self.cache_dtype
            )
            new_pool = self.model.paged.prefill_write(
                self.cfg, pool, slot_cache, table_row, self.pool.block_size
            )
            return logits, new_pool

        logits, new_pools = jax.vmap(member_prefill)(members, pools)  # (K,1,1,V)
        logp = mixture_logprobs(logits[:, 0, -1], self.bma)  # (V,)
        tok = select_tokens(logp, key, self.sampling)  # scalar
        slot_done = self._eos_hits(tok) | (max_new <= 1)
        feed = jnp.where(slot_done, jnp.int32(self.pad_id), tok)
        tokens = tokens.at[slot, 0].set(feed)
        done = done.at[slot].set(slot_done)
        budget = budget.at[slot].set(max_new - 1)
        return new_pools, tokens, done, budget, tok, slot_done, logp

    # -- serving loop -------------------------------------------------------

    def _finalize(self, slot, act: _Active, step: int, now: float, results: list):
        r = act.result
        r.tokens = np.asarray(act.tokens, np.int32)
        r.finished_step = step
        r.latency_s = now - act.submit_s
        r.hit_eos = self.eos_id is not None and r.num_tokens > 0 and int(r.tokens[-1]) == self.eos_id
        if self.record_logprobs:
            r.logprobs = np.asarray(act.logprobs, np.float32)
        results.append(r)
        self.pool.release(slot)
        obs_trace.get().instant(
            "serve.retire", cat="serve", rid=r.rid, slot=slot,
            tokens=r.num_tokens, eos=bool(r.hit_eos),
        )

    def _do_admit(self, req: Request, step: int, submit_s: float, active: dict, results: list,
                  wall, page_waits: int):
        need = int(req.prompt.size) + req.max_new
        if need > self.max_seq:
            # the non-windowed cache write clamps at max_seq-1, which would
            # silently corrupt the tail — refuse instead
            raise ValueError(
                f"request {req.rid}: prompt_len + max_new = {need} exceeds "
                f"engine max_seq={self.max_seq}"
            )
        slot = self.pool.acquire()
        tracer = obs_trace.get()
        # queued_ms: schedulable to admitted; page_waits: the ticks of it
        # spent as the FCFS head refused for pages (the rest waited for
        # slots).  The span holds the admit's own host work from here on
        admit_span = tracer.span(
            "serve.admit", cat="serve", rid=req.rid, slot=slot,
            prompt_len=int(req.prompt.size), step=step,
            queued_ms=(wall() - submit_s) * 1e3, page_waits=page_waits,
        )
        admit_span.__enter__()
        key = jax.random.fold_in(self._key_admit, req.rid)
        prompt = jnp.asarray(req.prompt)[None]
        if self.paged:
            table_row = self.pool.admit_blocks(
                slot, req.prompt, req.max_new, self.registry.version
            )
            out = self._admit(
                self._members(),
                self.pool.caches,
                self._tokens,
                self._done,
                self._budget,
                prompt,
                jnp.asarray(table_row),
                jnp.int32(slot),
                jnp.int32(req.max_new),
                key,
            )
        else:
            out = self._admit(
                self._members(),
                self.pool.caches,
                self._tokens,
                self._done,
                self._budget,
                prompt,
                jnp.int32(slot),
                jnp.int32(req.max_new),
                key,
            )
        self.pool.caches, self._tokens, self._done, self._budget, tok, slot_done, logp = out
        admit_span.__exit__(None, None, None)
        now = wall()
        # The host holds the first token once this fetch returns, after the
        # prefill ran on the device.  A ring event here would end the gap
        # that readers of the ring take as that wait (they read the next
        # event's start after serve.admit ends, and a span records its
        # start), so the fetch is labelled for the profiler alone and
        # serve.first_token is the first ring event after the admit.
        with tracer.annotate("serve.first_token.fetch"):
            first = int(tok)
        ready = wall()
        tracer.instant("serve.first_token", cat="serve", rid=req.rid, slot=slot,
                       wait_ms=(ready - now) * 1e3)
        res = RequestResult(rid=req.rid, prompt_len=int(req.prompt.size), admitted_step=step)
        res.first_token_s = now - submit_s
        res.first_token_ready_s = ready - submit_s
        act = _Active(result=res, submit_s=submit_s, tokens=[first])
        if self.record_logprobs:
            act.logprobs.append(np.asarray(logp))
        if bool(slot_done):
            self._finalize(slot, act, step, now, results)
        else:
            active[slot] = act

    def run(self, requests, *, max_steps: int | None = None) -> ServeReport:
        """Serve ``requests`` (a list of :class:`Request`) to completion.

        The loop per scheduler tick: (1) admit pending arrivals into free
        slots (prefill-on-admit, first token emitted), (2) pump the snapshot
        refresher (amortized: a whole sampler chunk lands once per
        ``refresh_every`` ticks, but its cost is spread over every tick in
        between), (3) one compiled decode step for the whole
        slot axis, (4) collect emissions, finalize and recycle finished
        slots.  Idle periods (no active slots, future arrivals) fast-forward
        the tick clock.  Hitting ``max_steps`` finalizes the in-flight
        requests with whatever they emitted (``truncated=True``) and
        recycles their slots; still-pending requests are simply dropped."""
        queue = FCFSQueue(requests)
        active: dict[int, _Active] = {}
        results: list[RequestResult] = []
        submit_s: dict[int, float] = {}
        page_waits: dict[int, int] = {}
        step = 0
        steps_at_start = self.decode_steps
        t0 = time.perf_counter()
        wall = lambda: time.perf_counter() - t0
        budget_steps = max_steps if max_steps is not None else 1 << 60
        while (len(queue) or active) and step < budget_steps:
            if not active and len(queue) and queue.next_arrival() > step:
                step = queue.next_arrival()  # idle: jump to the next arrival
            for r in queue.visible(step):
                submit_s.setdefault(r.rid, wall())  # schedulable => clock starts
            while self.pool.free_slots:
                req = queue.peek(step)
                if req is None:
                    break
                if not self.pool.can_admit(req.prompt, req.max_new, self.registry.version):
                    # FCFS head-of-line: not enough free pages for this
                    # request's worst-case growth — wait for completions to
                    # free pages.  If nothing is in flight no pages will
                    # ever free, so an empty-pool refusal is permanent.
                    if not active and self.pool.active_slots == 0:
                        raise ValueError(
                            f"request {req.rid}: prompt_len + max_new = "
                            f"{int(req.prompt.size) + req.max_new} can never fit the "
                            f"page pool (free={self.pool.alloc.free_blocks} blocks "
                            f"of {self.pool.block_size})"
                        )
                    page_waits[req.rid] = page_waits.get(req.rid, 0) + 1
                    self.page_waits += 1
                    break
                queue.pop()
                self._do_admit(req, step, submit_s[req.rid], active, results, wall,
                               page_waits.pop(req.rid, 0))
            if self.refresher is not None and self.refresh_every:
                # every tick: flip-if-ready + credit-paced micro-chunk
                # dispatch (one full chunk per refresh_every ticks) — no
                # single request ever eats a whole chunk
                self.refresher.pump(step)
            self._note_version()  # promotions (any source) invalidate stale prefixes
            if active:
                tracer = obs_trace.get()
                # the tick's span covers dispatch AND the emissions fetch —
                # the true per-tick wall time including device compute
                tick_args = {"step": step, "active": len(active)}
                if self.paged:
                    tick_args["kv_pages"] = self.pool.kv_pages(active)
                with tracer.span("serve.decode_tick", cat="serve", **tick_args):
                    with tracer.span("serve.tick.dispatch", cat="serve"):
                        key = jax.random.fold_in(self._key_decode, step)
                        if self.paged:
                            # Host-side growth first: make sure every live slot
                            # owns the page its fed token writes into, then ship
                            # the tables/positions as data.
                            for slot in active:
                                self.pool.ensure_decode_block(slot)
                            out = self._decode(
                                self._members(),
                                self.pool.caches,
                                self._tokens,
                                self._done,
                                self._budget,
                                # jnp.array COPIES (asarray may zero-copy alias
                                # the allocator's live numpy buffers, which
                                # mutate under the async dispatch —
                                # advance()/ensure_decode_block run before the
                                # tick's compute necessarily does)
                                jnp.array(self.pool.tables),
                                jnp.array(self.pool.ctx),
                                key,
                            )
                        else:
                            out = self._decode(
                                self._members(),
                                self.pool.caches,
                                self._tokens,
                                self._done,
                                self._budget,
                                key,
                            )
                    if self.paged:
                        for slot in active:  # fed token consumed position ctx
                            self.pool.advance(slot)
                    emit, feed, caches, done, budget, logp = out
                    self.pool.caches = caches
                    self._tokens, self._done, self._budget = feed, done, budget
                    self.decode_steps += 1
                    with tracer.span("serve.tick.fetch", cat="serve"):
                        emit_np = np.asarray(emit)
                        done_np = np.asarray(done)
                        logp_np = np.asarray(logp) if self.record_logprobs else None
                with tracer.span("serve.tick.collect", cat="serve"):
                    now = wall()
                    for slot, act in list(active.items()):
                        act.tokens.append(int(emit_np[slot]))
                        if self.record_logprobs:
                            act.logprobs.append(logp_np[slot])
                        if done_np[slot]:
                            self._finalize(slot, act, step, now, results)
                            del active[slot]
            step += 1
        if active:  # max_steps truncation: finalize + recycle in-flight slots
            self._done = self._done.at[jnp.asarray(sorted(active), jnp.int32)].set(True)
            now = wall()
            for slot, act in list(active.items()):
                act.result.truncated = True
                self._finalize(slot, act, step, now, results)
                del active[slot]
        results.sort(key=lambda r: r.rid)
        report = ServeReport(
            results=results,
            wall_s=wall(),
            decode_steps=self.decode_steps - steps_at_start,
            total_tokens=sum(r.num_tokens for r in results),
            trace_counts=dict(self.trace_counts),
            pool=self.pool.stats(),
            registry=self.registry.stats(),
            refresher=self.refresher.stats() if self.refresher else None,
        )
        self._absorb_metrics(report)
        return report

    def _absorb_metrics(self, report: ServeReport) -> None:
        """Fold the run's legacy stats() dicts + per-request latencies into
        the canonical metrics registry (DESIGN.md §11).  Host-side, once per
        run, on already-materialized values — no device syncs added."""
        reg = obs_metrics.default_registry()
        reg.absorb("serve.engine", {
            "decode_steps": self.decode_steps,
            "total_tokens": report.total_tokens,
            "retired": len(report.results),
            "wall_s": report.wall_s,
            "tokens_per_s": report.tokens_per_s,
        })
        if self.paged:
            # PagedCachePool.stats() merges the allocator dict in; absorb the
            # allocator under its own namespace and the rest under the pool's
            alloc = self.pool.alloc.stats()
            reg.absorb("serve.alloc", alloc)
            reg.absorb("serve.pool", {
                k: v for k, v in report.pool.items() if k not in alloc
            })
        else:
            reg.absorb("serve.pool", report.pool)
        reg.absorb("serve.registry", report.registry)
        reg.absorb("serve.admit", {"page_waits_total": self.page_waits})
        if report.refresher:
            reg.absorb("serve.refresh", report.refresher)
        lat = reg.histogram("serve.request.latency_s")
        ftl = reg.histogram("serve.request.first_token_s")
        for r in report.results:
            lat.observe(r.latency_s)
            ftl.observe(r.first_token_ready_s)
