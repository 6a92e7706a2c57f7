"""Device-resident chain executor: whole sampling runs as chunked
``lax.scan`` programs.

Every driver in this repo used to advance samplers one jitted step per
Python iteration, so measured "throughput" was host-dispatch latency, not
sampler math — fatal at the paper's Fig. 1/2 scale where a sampler step is
microseconds.  ``ChainExecutor`` compiles the entire step loop onto the
device:

* the inner loop is ``lax.scan`` over ``Sampler.{grad_targets, update}``
  (or a raw ``step_fn``), with the carry DONATED between chunks — params,
  sampler state and accumulators never round-trip to the host;
* streaming diagnostics ride the carry: Welford moments
  (``repro.diagnostics.moments``) and batch-means ESS
  (``repro.diagnostics.streaming``) accumulate with zero host syncs;
* traces are collected THINNED inside the program (nested scan), so a
  million-step run can keep every 100th sample without materializing the
  rest;
* the host regains control only at CHUNK boundaries — that is where
  ``train/loop.py`` checkpoints, logs, and honors preemption, preserving
  its auto-resume semantics exactly (DESIGN.md §3 states the contract);
* a SWEEP axis (``sweep=True`` / ``hyper=``) vmaps whole runs over stacked
  seeds or sampler hyperparameters — a benchmark grid becomes one compiled
  program;
* ``run_sharded`` routes the chain axis through ``shard_map`` over a mesh
  (``repro.distributed.sharding.chain_specs``): the s-periodic center sync
  stays the program's ONLY cross-chain collective, which
  ``tests/test_executor.py`` verifies on the lowered HLO.

Key modes (``key_mode``) reproduce the RNG streams of the drivers this
replaces, bit-for-bit:

* ``"keys"``  — caller pre-splits one key per step (the stationary battery
  and the toy benchmarks);
* ``"fold"``  — per-step key is ``fold_in(base_key, global_step)`` (the
  training loop; resume-safe since the step index is absolute);
* ``"carry"`` — a key rides the carry and is ``split`` once per step (the
  legacy posterior driver sequence).
"""
from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import apply_updates, tree_broadcast_axis0
from repro.obs import trace as obs_trace
from repro.diagnostics import (
    BatchMeansState,
    MomentState,
    batch_ess_add,
    batch_ess_estimate,
    batch_ess_init,
    welford_add,
    welford_init,
)


def _select_tree(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _is_typed_key(key) -> bool:
    return jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key)


class ChunkSnapshot(NamedTuple):
    """One chunk-boundary observation from ``ChainExecutor.stream``:
    ``step`` is the absolute step index at the boundary; ``params``/``state``
    are defensive copies by default (the live carry is donated into the next
    chunk's program, so holding the raw reference across iterations would be
    a use-after-donate).  ``probe`` is a copied device scalar (the carry's
    step counter) produced BY the chunk computation: ``probe.is_ready()``
    answers "has this chunk retired?" without a host sync — the
    backpressure signal the overlapped refresh scheduler paces dispatch
    with (DESIGN.md §9)."""

    step: int
    params: Any
    state: Any
    outs: Any
    probe: Any = None


class RunResult(NamedTuple):
    """Everything a driver can ask the executor for.  ``trace``/``stats``
    are time-major host arrays (sweep axis first when swept);
    ``moments``/``ess`` are the in-carry accumulators in their final state
    — feed them to ``diagnostics.welford_mean/var`` / ``batch_ess_estimate``."""

    params: Any
    state: Any
    trace: Any  # (T', ...) pytree or None
    stats: Any  # (T', ...) dict of scalars or None
    metrics: Any  # metrics dict of the final executed step ({} if none)
    moments: Optional[MomentState]
    ess: Optional[BatchMeansState]
    steps: int
    wall_s: float

    @property
    def steps_per_s(self) -> float:
        return self.steps / max(self.wall_s, 1e-12)


class ChainExecutor:
    """Compiles sampling runs as chunked, donated ``lax.scan`` programs.

    Exactly one of ``step_fn`` / ``sampler`` / ``sampler_factory`` drives
    the dynamics:

    * ``step_fn(params, state, batch, rng) -> (params, state, metrics)`` —
      arbitrary update (the training loop's model step);
    * ``sampler`` + ``grad_fn(targets, batch) -> grads | (grads, metrics)``
      — the Sampler protocol: gradients are evaluated at
      ``sampler.grad_targets(state, params)`` (stale snapshots for
      approach-I samplers) and fed to ``sampler.update``;
    * ``sampler_factory(hyper) -> Sampler`` — as above, but constructed
      inside the traced program from a (possibly vmapped) hyperparameter
      pytree: an (alpha, step_size, ...) grid runs as ONE compiled program.
      Structural hyperparameters (``sync_every``, chain count, dtypes)
      change the program and must stay Python-static — DESIGN.md §3.

    ``chunk_steps`` bounds how long the device runs between host visits;
    checkpointing/logging/preemption can only happen there.  When tracing
    (``trace_fn``), ``chunk_steps`` and ``num_steps`` must be multiples of
    ``thin``.  Without a ``trace_fn`` the chunk is a single flat scan and
    ``stats``/``metrics`` are reported once per chunk (the final step's).
    """

    def __init__(
        self,
        *,
        step_fn: Callable | None = None,
        sampler=None,
        sampler_factory: Callable | None = None,
        grad_fn: Callable | None = None,
        batch_fn: Callable | None = None,  # host: step -> batch (stacked per chunk)
        device_batch_fn: Callable | None = None,  # traced: step -> batch
        trace_fn: Callable | None = None,  # params -> trace point
        thin: int = 1,
        moments: bool = False,
        moments_of: Callable | None = None,  # params -> tree to accumulate
        moments_from: int = 0,
        ess_probe_fn: Callable | None = None,  # params -> small probe array
        ess_batch_len: int = 64,
        collect_stats: bool = False,
        chunk_steps: int = 256,
        donate: bool = True,
        key_mode: str = "keys",
    ):
        if sum(x is not None for x in (step_fn, sampler, sampler_factory)) != 1:
            raise ValueError("exactly one of step_fn / sampler / sampler_factory")
        if (sampler is not None or sampler_factory is not None) and grad_fn is None:
            raise ValueError("sampler mode needs grad_fn")
        if key_mode not in ("keys", "fold", "carry"):
            raise ValueError(f"unknown key_mode {key_mode!r}")
        if batch_fn is not None and device_batch_fn is not None:
            raise ValueError("pass either batch_fn (host) or device_batch_fn (traced)")
        if thin < 1 or chunk_steps < 1:
            raise ValueError("thin and chunk_steps must be >= 1")
        if trace_fn is not None and chunk_steps % thin != 0:
            raise ValueError("chunk_steps must be a multiple of thin when tracing")
        self.step_fn = step_fn
        self.sampler = sampler
        self.sampler_factory = sampler_factory
        self.grad_fn = grad_fn
        self.batch_fn = batch_fn
        self.device_batch_fn = device_batch_fn
        self.trace_fn = trace_fn
        self.thin = int(thin)
        self.moments = moments
        self.moments_of = moments_of or (lambda p: p)
        self.moments_from = int(moments_from)
        self.ess_probe_fn = ess_probe_fn
        self.ess_batch_len = int(ess_batch_len)
        self.collect_stats = collect_stats
        self.chunk_steps = int(chunk_steps)
        self.donate = donate
        self.key_mode = key_mode
        self._compiled: dict = {}

    # -- step construction --------------------------------------------------

    def _resolve(self, hyper):
        """(step, stats_fn) for a given (possibly traced) hyper pytree."""
        if self.step_fn is not None:
            return self.step_fn, None
        sampler = self.sampler if self.sampler is not None else self.sampler_factory(hyper)
        grad_fn = self.grad_fn

        def step(params, state, batch, rng):
            targets = (
                sampler.grad_targets(state, params) if sampler.grad_targets else params
            )
            out = grad_fn(targets, batch)
            grads, metrics = out if isinstance(out, tuple) else (out, {})
            updates, new_state = sampler.update(grads, state, params, rng)
            return apply_updates(params, updates), new_state, metrics

        return step, sampler.stats

    # -- chunk program ------------------------------------------------------

    def _build_chunk(self, n: int):
        """chunk(hyper, base_key, carry, xs) -> (carry, outs), advancing
        ``n`` steps as (n // thin) outer x thin inner scan iterations."""
        thin = self.thin if self.trace_fn is not None else n
        n_outer = n // thin

        def chunk(hyper, base_key, carry, xs):
            step, stats_fn = self._resolve(hyper)

            def inner(c, x):
                t = c["t"]
                new_key = c["key"]
                if self.key_mode == "keys":
                    rng = x["key"]
                elif self.key_mode == "fold":
                    rng = jax.random.fold_in(base_key, t)
                else:  # carry: key, sub = split(key) — legacy driver sequence
                    ks = jax.random.split(c["key"])
                    new_key, rng = ks[0], ks[1]
                batch = (
                    x["batch"]
                    if self.batch_fn is not None
                    else (self.device_batch_fn(t) if self.device_batch_fn else None)
                )
                params, state, metrics = step(c["params"], c["state"], batch, rng)
                c = dict(c, params=params, state=state, t=t + 1, key=new_key)
                live = t >= self.moments_from
                if self.moments:
                    wf2 = welford_add(c["wf"], self.moments_of(params))
                    c["wf"] = _select_tree(live, wf2, c["wf"])
                if self.ess_probe_fn is not None:
                    es2 = batch_ess_add(c["ess"], self.ess_probe_fn(params))
                    c["ess"] = _select_tree(live, es2, c["ess"])
                return c, metrics

            def outer(c, x):
                c, mseq = jax.lax.scan(inner, c, x, length=thin)
                outs = {"metrics": jax.tree.map(lambda a: a[-1], mseq)}
                if self.trace_fn is not None:
                    outs["trace"] = self.trace_fn(c["params"])
                if self.collect_stats and stats_fn is not None:
                    outs["stats"] = stats_fn(c["state"], c["params"])
                return c, outs

            return jax.lax.scan(outer, carry, xs, length=n_outer)

        return chunk, n_outer, thin

    def _compile(self, n: int, sweep: bool, key_axis):
        sig = (n, sweep, key_axis)
        if sig in self._compiled:
            return self._compiled[sig]
        chunk, n_outer, thin = self._build_chunk(n)
        fn = chunk
        if sweep:
            # hyper / carry / xs map over their leading axis; base_key only
            # when the caller stacked per-member keys (key_axis=0)
            fn = jax.vmap(chunk, in_axes=(0, key_axis, 0, 0))
        fn = jax.jit(fn, donate_argnums=(2,) if self.donate else ())
        self._compiled[sig] = (fn, n_outer, thin)
        return fn, n_outer, thin

    # -- host driver --------------------------------------------------------

    @staticmethod
    def _sweep_size(tree) -> int:
        return jax.tree.leaves(tree)[0].shape[0]

    def _init_carry(self, params, state, start_step, key, sweep):
        p1 = jax.tree.map(lambda x: x[0], params) if sweep else params
        carry = {
            "params": params,
            "state": state,
            "t": jnp.asarray(start_step, jnp.int32),
            "key": None,
            "wf": None,
            "ess": None,
        }
        stack = (lambda tr: tree_broadcast_axis0(tr, self._sweep_size(params))) if sweep else (lambda tr: tr)
        if sweep:
            carry["t"] = stack(carry["t"])
        if self.moments:
            carry["wf"] = stack(welford_init(jax.eval_shape(self.moments_of, p1)))
        if self.ess_probe_fn is not None:
            probe = jax.eval_shape(self.ess_probe_fn, p1)
            carry["ess"] = stack(batch_ess_init(probe, self.ess_batch_len))
        if self.key_mode == "carry":
            carry["key"] = key  # caller stacks it in sweep mode
        return carry

    def _chunk_xs(self, t_run: int, t_abs: int, n: int, thin: int, keys, sweep):
        """Per-chunk xs with (n_outer, thin) step axes (after the sweep
        axis, when present)."""
        n_outer = n // thin
        xs = {}
        if self.key_mode == "keys":
            if sweep:
                sl = keys[:, t_run : t_run + n]
                xs["key"] = sl.reshape(sl.shape[:1] + (n_outer, thin) + sl.shape[2:])
            else:
                sl = keys[t_run : t_run + n]
                xs["key"] = sl.reshape((n_outer, thin) + sl.shape[1:])
        if self.batch_fn is not None:
            if sweep:
                raise NotImplementedError("host batch_fn + sweep is unsupported")
            batches = [self.batch_fn(t_abs + i) for i in range(n)]
            stacked = jax.tree.map(lambda *bs: jnp.stack(bs), *batches)
            xs["batch"] = jax.tree.map(
                lambda a: a.reshape((n_outer, thin) + a.shape[1:]), stacked
            )
        return xs

    def run(
        self,
        params,
        state,
        *,
        num_steps: int,
        key=None,
        keys=None,
        start_step: int = 0,
        hyper=None,
        sweep: bool | None = None,
        on_chunk: Callable | None = None,
        adapt_fn: Callable | None = None,
    ) -> RunResult:
        """Advance ``num_steps`` steps from ``(params, state)``.

        ``keys``: (num_steps, ...) per-step RNG keys for ``key_mode="keys"``
        (``(S, num_steps, ...)`` when swept); ``key``: base key for
        ``"fold"``/``"carry"``.  ``start_step``: absolute index of the first
        step (resume; drives ``fold_in``, ``batch_fn`` and schedules
        through the sampler's own step counter).  ``sweep``: vmap the run
        over the leading axis of params/state/keys/hyper (default: implied
        by ``hyper``; pass ``sweep=False`` to use an UNSWEPT hyper pytree —
        the adaptation configuration).  ``on_chunk(step_end, params, state,
        outs)`` runs on the host at every chunk boundary; return False to
        stop early.

        ``adapt_fn(step_end, carry, hyper) -> hyper | None`` is the
        ADAPTATION HOOK: called on the host at every chunk boundary (before
        the next chunk launches); a non-None return replaces ``hyper`` for
        the remaining chunks.  Because hyper values enter the compiled chunk
        as traced scalars, changing their VALUES never retraces — the hook
        must preserve their avals (keep jnp.float32 scalars jnp.float32).
        This is how ``schedules.FeedbackESS`` closes the diagnostics →
        dynamics loop: read the in-carry streaming ESS, call
        ``controller.update()``, and hand the new step size to the next
        chunk (see ``ess_feedback_adapter``).

        The carry is DONATED between chunks: buffers passed in are consumed
        (pass copies if you need them after).
        """
        sweep = (hyper is not None) if sweep is None else bool(sweep)
        if self.sampler_factory is not None and hyper is None:
            raise ValueError("sampler_factory mode needs hyper=")
        if self.key_mode == "keys" and keys is None:
            raise ValueError("key_mode='keys' needs keys=")
        if self.key_mode in ("fold", "carry") and key is None:
            raise ValueError(f"key_mode={self.key_mode!r} needs key=")
        if self.trace_fn is not None and num_steps % self.thin != 0:
            raise ValueError("num_steps must be a multiple of thin when tracing")
        key_axis = None
        if sweep and self.key_mode == "fold":
            stacked = key.ndim >= 1 if _is_typed_key(key) else key.ndim >= 2
            key_axis = 0 if stacked else None

        carry = self._init_carry(params, state, start_step, key, sweep)
        traces, stats, metrics = [], [], {}
        t_run, t_abs = 0, int(start_step)
        t0 = time.perf_counter()
        stopped = False
        while t_run < num_steps and not stopped:
            n = min(self.chunk_steps, num_steps - t_run)
            fn, n_outer, thin = self._compile(n, sweep, key_axis)
            xs = self._chunk_xs(t_run, t_abs, n, thin, keys, sweep)
            # the span measures host-side DISPATCH (async enqueue), not
            # device compute — executor.settle below is where compute lands
            with obs_trace.get().span("executor.chunk", cat="executor",
                                      step=t_abs, n=n):
                carry, outs = fn(hyper, key, carry, xs)
            t_run += n
            t_abs += n
            if self.trace_fn is not None:
                traces.append(outs["trace"])
            if "stats" in outs:
                stats.append(outs["stats"])
            metrics = jax.tree.map(
                (lambda a: a[:, -1]) if sweep else (lambda a: a[-1]), outs["metrics"]
            )
            if on_chunk is not None:
                if on_chunk(t_abs, carry["params"], carry["state"], outs) is False:
                    stopped = True
            if adapt_fn is not None and t_run < num_steps and not stopped:
                new_hyper = adapt_fn(t_abs, carry, hyper)
                if new_hyper is not None:
                    hyper = new_hyper
        # dispatch is async: settle the final carry (same executable as the
        # chunk outputs) so wall_s measures compute, not enqueue latency
        with obs_trace.get().span("executor.settle", cat="executor", step=t_abs):
            jax.block_until_ready(carry["params"])
        wall = time.perf_counter() - t0

        axis = 1 if sweep else 0
        cat = lambda ts: jax.tree.map(lambda *xs_: np.concatenate(xs_, axis=axis), *ts)
        return RunResult(
            params=carry["params"],
            state=carry["state"],
            trace=cat(traces) if traces else None,
            stats=cat(stats) if stats else None,
            metrics=metrics,
            moments=carry["wf"],
            ess=carry["ess"],
            steps=t_run,
            wall_s=wall,
        )

    def stream(
        self,
        params,
        state,
        *,
        num_steps: int,
        key=None,
        keys=None,
        start_step: int = 0,
        copy_snapshots: bool = True,
        snapshot_every: int = 1,
    ):
        """Chunk-boundary snapshot hook: a generator that advances the run
        one chunk at a time and yields a :class:`ChunkSnapshot` at every
        boundary — the host-side surface the serving tier's snapshot
        registry refreshes ensemble members from (`repro.serve.engine`).

        Unlike ``run`` nothing is accumulated across chunks: the caller owns
        each boundary.  With ``copy_snapshots`` (default) the yielded
        params/state are copies and stay valid after the generator advances;
        pass False only if each snapshot is fully consumed before ``next()``
        is called again — the live carry is donated into the next chunk.
        The generator can be abandoned at any boundary (the carry's device
        buffers are garbage-collected with it).

        ``snapshot_every=k`` is the MICRO-CHUNK hook (DESIGN.md §9): every
        boundary still yields (so a caller can pace dispatch one chunk at a
        time against another workload's clock), but params/state are copied
        only on every k-th boundary and on the final one — intermediate
        yields carry ``params=state=None``.  Chunking is invisible to the
        dynamics (§3), so splitting a chunk into k micro-chunks with
        ``key_mode='fold'`` is bit-identical to the unsplit run.  Nothing
        in this generator forces a host sync: every chunk dispatch, copy and
        yield rides JAX's async dispatch."""
        if self.key_mode == "keys" and keys is None:
            raise ValueError("key_mode='keys' needs keys=")
        if self.key_mode in ("fold", "carry") and key is None:
            raise ValueError(f"key_mode={self.key_mode!r} needs key=")
        if self.trace_fn is not None and num_steps % self.thin != 0:
            raise ValueError("num_steps must be a multiple of thin when tracing")
        if self.sampler_factory is not None:
            raise ValueError("stream does not support sampler_factory mode")
        if snapshot_every < 1:
            raise ValueError("snapshot_every must be >= 1")
        copy = (lambda tr: jax.tree.map(lambda x: x.copy(), tr)) if copy_snapshots else (lambda tr: tr)
        carry = self._init_carry(params, state, start_step, key, sweep=False)
        t_run, t_abs, boundary = 0, int(start_step), 0
        while t_run < num_steps:
            n = min(self.chunk_steps, num_steps - t_run)
            fn, n_outer, thin = self._compile(n, False, None)
            xs = self._chunk_xs(t_run, t_abs, n, thin, keys, False)
            with obs_trace.get().span("executor.chunk", cat="executor",
                                      step=t_abs, n=n, stream=True):
                carry, outs = fn(None, key, carry, xs)
            t_run += n
            t_abs += n
            boundary += 1
            # the copy makes the probe safe to hold across the next chunk
            # when that chunk donates (and deletes) the carry; a non-donated
            # stream can hand out the scalar itself — one less dispatch on
            # the caller's (possibly latency-critical) thread
            probe = carry["t"].copy() if self.donate else carry["t"]
            if boundary % snapshot_every == 0 or t_run >= num_steps:
                yield ChunkSnapshot(t_abs, copy(carry["params"]), copy(carry["state"]), outs, probe)
            else:
                yield ChunkSnapshot(t_abs, None, None, outs, probe)

    # -- shard_map chain routing -------------------------------------------

    def _build_sharded(self, n, mesh, chain_axis, carry, num_chains, specs=None):
        """Jitted shard_map chunk: the carry shards on the chain axis via
        the ``chain_specs`` shape contract.  The per-step key is
        SHARD-INVARIANT: the sampler must have been built with
        ``chain_axis=<name>``, which makes it (a) reduce its sync mean over
        that axis (pmean, or one packed-int8 all_gather when built with
        ``compression=`` — the wire-compressed center exchange) and
        (b) key its per-chain noise by the GLOBAL chain index — per-chain
        noise decorrelates across shards and is invariant to the mesh
        layout, while replicated center state sees identical noise
        everywhere (DESIGN.md §2/§7).  No per-step outputs — the production
        configuration keeps moments in the carry and nothing else leaves
        the device."""
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        from repro.distributed.sharding import chain_specs

        if specs is None:
            specs = chain_specs(carry, num_chains, chain_axis)

        def chunk(base_key, carry):
            step, _ = self._resolve(None)

            def body(c, _):
                t = c["t"]
                # shard-invariant by design: the chain_axis sampler folds the
                # shard index into its per-chain noise keys itself, keeping
                # center-noise draws replicated (DESIGN.md §2)
                rng = jax.random.fold_in(base_key, t)
                batch = self.device_batch_fn(t) if self.device_batch_fn else None
                params, state, _m = step(c["params"], c["state"], batch, rng)
                c = dict(c, params=params, state=state, t=t + 1)
                if self.moments:
                    wf2 = welford_add(c["wf"], self.moments_of(params))
                    c["wf"] = _select_tree(t >= self.moments_from, wf2, c["wf"])
                return c, None

            c, _ = jax.lax.scan(body, carry, None, length=n)
            return c

        sm = shard_map(
            chunk, mesh=mesh, in_specs=(P(), specs), out_specs=specs, check_rep=False
        )
        return jax.jit(sm, donate_argnums=(1,) if self.donate else ())

    def _sharded_carry(self, params, state, start_step):
        carry = self._init_carry(params, state, start_step, None, sweep=False)
        carry.pop("key")
        carry.pop("ess")  # probe shapes are global; keep the sharded carry minimal
        return carry

    @staticmethod
    def _check_mesh(mesh, chain_axis: str, num_chains: int) -> None:
        """Multi-device contract (DESIGN.md §7): the chain axis must exist
        on the mesh and divide K evenly — equal per-shard chain counts are
        what make the hierarchical (local mean, cross-shard mean) exchange
        equal the global chain mean."""
        if chain_axis not in mesh.shape:
            raise ValueError(
                f"mesh has axes {tuple(mesh.shape)}; no {chain_axis!r} axis"
            )
        axis_size = mesh.shape[chain_axis]
        if num_chains % axis_size != 0:
            raise ValueError(
                f"num_chains={num_chains} must be divisible by the "
                f"{chain_axis!r} mesh axis (size {axis_size})"
            )

    def run_sharded(
        self,
        params,
        state,
        *,
        num_steps: int,
        key,
        mesh,
        chain_axis: str = "chain",
        num_chains: int | None = None,
        start_step: int = 0,
        specs=None,
    ) -> RunResult:
        """Device-resident run with the chain axis sharded over ``mesh``
        (chunked like ``run``; no traces/stats — moments stay in carry).

        ``mesh`` may carry a ``chain_axis`` of ANY size that divides the
        chain count — 1 (the SPMD emulation) through one device per chain.
        The compiled program is layout-invariant for samplers built with
        ``chain_axis=``: per-chain trajectories are bit-identical across
        mesh sizes wherever reduction order allows (DESIGN.md §7, gated by
        tests/test_sharding.py).

        ``specs``: explicit carry PartitionSpec pytree, overriding the
        ``chain_specs`` shape heuristic — REQUIRED when replicated state has
        a leading dim that coincidentally equals ``num_chains`` (the
        heuristic would shard it; see ``chain_specs``' docstring)."""
        num_chains = num_chains or self._sweep_size(params)
        self._check_mesh(mesh, chain_axis, num_chains)
        carry = self._sharded_carry(params, state, start_step)
        t0 = time.perf_counter()
        done = 0
        while done < num_steps:
            n = min(self.chunk_steps, num_steps - done)
            sig = ("sharded", n, chain_axis, id(mesh))
            if sig not in self._compiled:
                self._compiled[sig] = self._build_sharded(
                    n, mesh, chain_axis, carry, num_chains, specs
                )
            with obs_trace.get().span("executor.chunk", cat="executor",
                                      step=done, n=n, sharded=True):
                carry = self._compiled[sig](key, carry)
            done += n
        with obs_trace.get().span("executor.settle", cat="executor", step=done):
            jax.block_until_ready(carry["params"])
        wall = time.perf_counter() - t0
        return RunResult(
            params=carry["params"], state=carry["state"], trace=None, stats=None,
            metrics={}, moments=carry["wf"], ess=None, steps=done, wall_s=wall,
        )

    def lower_sharded(self, params, state, *, num_steps, key, mesh,
                      chain_axis: str = "chain", num_chains: int | None = None,
                      specs=None):
        """Lowered (pre-compile) sharded chunk for HLO inspection — the
        one-collective-per-sync-period acceptance check reads its text
        (raw center exchange: one all-reduce; compressed: one all-gather)."""
        num_chains = num_chains or self._sweep_size(params)
        self._check_mesh(mesh, chain_axis, num_chains)
        carry = self._sharded_carry(params, state, 0)
        fn = self._build_sharded(num_steps, mesh, chain_axis, carry, num_chains, specs)
        return fn.lower(key, carry)


def ess_feedback_adapter(controller, hyper_key: str = "step_size"):
    """Bridge a ``schedules.FeedbackESS`` controller to the executor's
    ``adapt_fn`` hook: at each chunk boundary, turn the in-carry batch-means
    ESS into an ESS-per-step rate, feed it to ``controller.update``, and
    hand the controller's new value back through ``hyper[hyper_key]``.

    Requires the executor to be built with ``ess_probe_fn`` (the streaming
    ESS accumulator must ride the carry) and the sampler to be built via
    ``sampler_factory`` reading ``hyper[hyper_key]``.  The replacement value
    is always a jnp.float32 scalar — same aval every chunk, so the compiled
    scan NEVER retraces (pinned by tests/test_executor.py)."""

    def adapt(step_end, carry, hyper):
        es = carry.get("ess")
        if es is None:
            raise ValueError("ess_feedback_adapter requires an executor with ess_probe_fn")
        count = float(np.asarray(es.count))
        if count < 2.0 * float(np.asarray(es.batch_len)):
            return None  # need >= 2 complete batches for a defensible estimate
        ess = np.asarray(batch_ess_estimate(es))
        controller.update(float(np.mean(ess)) / max(count, 1.0), step=step_end)
        new_hyper = dict(hyper or {})
        new_hyper[hyper_key] = jnp.asarray(controller.value, jnp.float32)
        return new_hyper

    return adapt


def rollout(
    sampler,
    grad_fn,
    params,
    *,
    num_steps: int,
    keys=None,
    key=None,
    state=None,
    trace: bool = True,
    thin: int = 1,
    moments: bool = True,
    moments_from: int = 0,
    chunk_steps: int = 4096,
    key_mode: str = "keys",
    sweep: bool = False,
    **kw,
) -> RunResult:
    """One-call executor run for sampler-over-potential workloads (the test
    battery, toy benchmarks, ensemble collection).  ``grad_fn(theta)`` takes
    only the gradient targets — batch plumbing belongs to the training
    stack."""
    if chunk_steps % thin != 0:
        chunk_steps = thin * max(chunk_steps // thin, 1)
    ex = ChainExecutor(
        sampler=sampler,
        grad_fn=lambda targets, _batch: grad_fn(targets),
        trace_fn=(lambda p: p) if trace else None,
        thin=thin,
        moments=moments,
        moments_from=moments_from,
        chunk_steps=chunk_steps,
        key_mode=key_mode,
        **kw,
    )
    if state is None:
        init = jax.vmap(sampler.init) if sweep else sampler.init
        state = init(params)
    return ex.run(params, state, num_steps=num_steps, keys=keys, key=key, sweep=sweep)
