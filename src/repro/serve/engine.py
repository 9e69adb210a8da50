"""ServeEngine: continuous-batching serving replica over slot-stacked caches.

One engine owns one model replica's device state and drives it with the
host-side :class:`repro.serve.scheduler.Scheduler`:

* **slot-stacked caches** — the decode cache is a pytree whose every
  leaf carries a *slot* axis over an inner B=1 cache (after the layer
  axis on the layer-stacked leaves: see
  :func:`repro.serve.decode.slot_axes`), so membership changes are
  per-row scatters and the decode batch shape never retraces; the slice
  and the scatters donate the cache, so it is never held twice;
* **bucketed prefill** — each admitted request prefills alone (B=1) in
  a jitted program compiled per *bucket length*, not per prompt length:
  the prompt rides padded to its :class:`PromptBuckets` bucket.  Where
  the model has a parallel form (``Model.prefill``) the whole padded
  prompt runs through the stack in one causal forward pass; padding
  cannot reach a real row, since the causal mask keeps every real
  position from attending a later one and the cache marks pad rows
  ``pos = -1``, which decode never attends.  Other models (recurrent
  state, ring buffers, MoE, encoder extras) step the prompt through
  ``decode_step``, where a where-snapshot keeps only the state after
  exactly ``len(prompt)`` real steps, bitwise what an unpadded prefill
  gives;
* **sliced decode** — between membership boundaries the engine runs one
  jitted :func:`repro.serve.decode.make_decode_slice` step (a
  ``while_loop`` of up to ``slice_len`` tokens with the psum-min EOS
  early exit); with a mesh the slice runs inside ``shard_map`` over the
  serving group's joint axes with the slot axis sharded and the logits
  head tensor-parallel through ``CommContext`` routing.

Its programs compile under stable names (``jit_prefill``,
``jit_slice_fn``, ``jit_scatter``, ``jit_set_tok``), :attr:`ServeEngine.
traces` counts how often each was traced, and :meth:`ServeEngine.step`
marks its host work with ``repro.serve.*`` spans
(:mod:`repro.runtime.tracing`).

The slot count is ragged over the serving group
(:meth:`Scheduler.shard_geometry`, i.e. ``napalg.ragged_splits``); the
executed lowering pads every chip to ``max(geometry)`` rows — repo
idiom: ragged at the accounting layer, padded at execution — and the
scheduler simply never fills the pad slots.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import comm
from ..runtime.tracing import span
from . import decode as _decode
from .scheduler import PromptBuckets, Request, Scheduler

__all__ = ["ServeEngine"]


class ServeEngine:
    """Continuous-batching engine for one serving replica.

    Args:
      model: a :class:`repro.models.Model` (needs the decode pair).
      params: model parameters (placed replicated on the mesh if given).
      num_slots: logical decode batch width (the scheduler's slot
        count).  With a mesh this is padded up to a multiple of the
        group size for the executed lowering; the pad slots are never
        scheduled.
      max_len: KV/state cache length per slot.
      buckets: padded prompt-length buckets (default: geometric up to
        ``max_len``).
      eos_id: early-exit token (None disables EOS handling).
      slice_len: decode steps per jitted slice; membership changes only
        at slice boundaries, so this is the admission latency in tokens
        (default 1: per-token boundaries, the continuous-batching
        ideal).
      mesh / ctx: serving group.  With a mesh the slice is shard_mapped
        over the mesh's joint axes and the head is tensor-parallel.
      max_queue: admission-control bound (None = unbounded).
      extras_template: abstract pytree (shape/dtype) of per-request
        extras (e.g. encoder ``frames``) for enc-dec archs; requests
        must then carry matching ``extras``.
    """

    def __init__(
        self,
        model,
        params,
        *,
        num_slots: int,
        max_len: int,
        buckets: PromptBuckets | None = None,
        eos_id: int | None = None,
        slice_len: int = 1,
        mesh=None,
        ctx: comm.CommContext | None = None,
        max_queue: int | None = None,
        extras_template: dict | None = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.model = model
        if mesh is not None:
            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        self.max_len = int(max_len)
        self.slice_len = int(slice_len)
        self.eos_id = eos_id
        self.mesh = mesh
        self.clock = clock
        self.extras_template = extras_template
        if buckets is None:
            buckets = PromptBuckets.geometric(self.max_len)
        self.scheduler = Scheduler(
            num_slots, max_queue=max_queue, buckets=buckets, eos_id=eos_id
        )

        if mesh is not None and ctx is None:
            ctx = comm.CommContext(comm.Topology.from_mesh(mesh))
        self.ctx = ctx
        # without a mesh there is no shard_map to bind axes, so the
        # slice must trace collective-free even if a ctx was passed
        self._slice_ctx = ctx if mesh is not None else None
        self.group = ctx.topology.group if (ctx and mesh is not None) else 1
        # ragged slot geometry over the group; executed lowering pads
        # every chip to the max block
        geometry = self.scheduler.shard_geometry(self.group)
        self.b_max = max(geometry)
        self.padded_slots = self.b_max * self.group

        # -- device state --------------------------------------------------
        self._cache = self._init_slot_cache()
        self._cache_axes = _decode.slot_axes(self._cache)
        self._tok = jnp.zeros((self.padded_slots, 1), jnp.int32)
        self._active = jnp.zeros((self.padded_slots,), bool)

        # -- compiled programs ---------------------------------------------
        # traces of each program ("slice", "prefill/<bucket>", "scatter",
        # "set_tok"): steady-state steps leave it unchanged
        self.traces: collections.Counter[str] = collections.Counter()
        self._prefills: dict[Any, Callable] = {}  # bucket key -> jitted fn
        self._prefill_mode = (
            "stepwise" if model.prefill is None else "parallel"
        )
        # admissions prefilled, by mode ("parallel" or "stepwise")
        self.prefills: collections.Counter[str] = collections.Counter()
        self._slice = self._build_slice()
        # stacked leaf rows have exactly the B=1 leaf's shape, so the
        # scatter is a plain per-row set on every leaf.  The compiled
        # programs close over locals, never over ``self``: an engine in a
        # reference cycle would hold its params and cache on the device
        # until the cyclic collector happens to run.
        cache_axes, traces = self._cache_axes, self.traces

        def scatter(full, one, row):
            traces["scatter"] += 1
            return {
                k: jax.tree.map(
                    lambda f, o, ax=ax: f.at[(slice(None),) * ax + (row,)].set(o),
                    full[k], one[k],
                )
                for k, ax in cache_axes.items()
            }

        def set_tok(tok, active, row, t):
            traces["set_tok"] += 1
            return tok.at[row, 0].set(t), active.at[row].set(True)

        self._scatter = jax.jit(scatter, donate_argnums=(0,))
        self._set_tok = jax.jit(set_tok, donate_argnums=(0, 1))

        # -- accounting ----------------------------------------------------
        self.step_times: list[tuple[int, float, int]] = []  # fit-shaped rows
        self.n_slices = 0
        self.n_decode_steps = 0

    # -- device-state construction -----------------------------------------

    def _b1_extras(self):
        if self.extras_template is None:
            return None
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.extras_template
        )

    def _init_slot_cache(self):
        """Slot-stacked cache: every leaf gets a slot axis over an inner
        B=1 cache (the scalar ``index`` becomes ``(slots,)``).  With a
        mesh it is created already sharded over the slot axis."""

        def init(params):
            b1 = self.model.init_decode(
                params, 1, max_len=self.max_len, batch=self._b1_extras()
            )
            return _decode.stack_slot_cache(b1, self.padded_slots)

        if self.mesh is None:
            return jax.jit(init)(self.params)
        specs = self._slot_specs(jax.eval_shape(init, self.params))
        shardings = {k: NamedSharding(self.mesh, p) for k, p in specs.items()}
        return jax.jit(init, out_shardings=shardings)(self.params)

    def _slot_specs(self, cache) -> dict:
        """``PartitionSpec`` per cache entry sharding its slot axis over
        the serving group (a pytree prefix of the cache)."""
        joint = self.ctx.topology.axes
        return {
            k: P(*(None,) * ax, joint)
            for k, ax in _decode.slot_axes(cache).items()
        }

    # -- compiled programs ---------------------------------------------------

    def _build_slice(self):
        decode_slice = _decode.make_decode_slice(
            self.model, self._slice_ctx,
            slice_len=self.slice_len, eos_id=self.eos_id,
        )
        traces = self.traces

        def slice_fn(params, cache, tok, active):
            traces["slice"] += 1
            return decode_slice(params, cache, tok, active)

        # the slice replaces the cache and the carry token
        donate = (1, 2)
        if self.mesh is None:
            return jax.jit(slice_fn, donate_argnums=donate)
        joint = self.ctx.topology.axes
        spec = P(joint)
        cache_spec = self._slot_specs(self._cache)
        fn = jax.shard_map(
            slice_fn,
            mesh=self.mesh,
            in_specs=(P(), cache_spec, spec, spec),
            # the step count is group-agreed (early exit is min-reduced)
            out_specs=(spec, spec, cache_spec, P()),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=donate)

    def _prefill_fn(self, bucket_len: int):
        """Jitted B=1 bucketed prefill: ``(params, prompt (1, L), n_real,
        extras or None) -> (cache, first token (1,))``, one compiled
        trace per bucket length.

        With ``model.prefill`` the padded prompt runs through the stack
        in one causal forward pass: each layer writes K and V of all L
        positions into the cache, marks positions ``>= n_real`` with
        ``pos = -1`` and sets ``index = n_real``, and the head runs on
        row ``n_real - 1`` alone.  No real row sees the padding (causal
        mask here, ``pos = -1`` in decode); sums associate differently
        from the token loop, so the result is close to it, not bitwise.

        Without it, teacher-forces the padded prompt through
        ``decode_step`` inside a ``fori_loop``; a scalar ``keep = t <
        n_real`` where-snapshot on (logits, cache) freezes the state
        after exactly ``n_real`` real steps, so the result is bitwise
        what an unpadded prefill of the true prompt produces.
        """
        model, max_len, traces = self.model, self.max_len, self.traces

        if model.prefill is not None:
            def prefill(params, prompt, n_real, extras):
                traces[f"prefill/{bucket_len}"] += 1
                cache, logits = model.prefill(params, prompt, n_real, max_len)
                return cache, jnp.argmax(logits, axis=-1).astype(jnp.int32)

            return jax.jit(prefill)

        def prefill(params, prompt, n_real, extras):
            traces[f"prefill/{bucket_len}"] += 1
            cache = model.init_decode(
                params, 1, max_len=max_len, batch=extras
            )
            logits0 = jnp.zeros((1, 1, model.cfg.vocab_size), jnp.float32)

            def body(t, carry):
                logits, cache = carry
                step_tok = jax.lax.dynamic_slice(prompt, (0, t), (1, 1))
                new_logits, new_cache = model.decode_step(
                    params, cache, step_tok
                )
                keep = t < n_real
                logits = jnp.where(keep, new_logits, logits)
                cache = jax.tree.map(
                    lambda a, b: jnp.where(keep, a, b), new_cache, cache
                )
                return logits, cache

            logits, cache = jax.lax.fori_loop(
                0, bucket_len, body, (logits0, cache)
            )
            tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
            return cache, tok

        return jax.jit(prefill)

    def _prefill(self, req: Request):
        if req.bucket_len not in self._prefills:
            self._prefills[req.bucket_len] = self._prefill_fn(req.bucket_len)
        prompt = np.zeros((1, req.bucket_len), np.int32)
        prompt[0, : len(req.prompt)] = req.prompt
        n_real = jnp.asarray(len(req.prompt), jnp.int32)
        return self._prefills[req.bucket_len](
            self.params, jnp.asarray(prompt), n_real, req.extras
        )

    # -- request lifecycle ---------------------------------------------------

    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        arrival: float | None = None,
        extras: dict | None = None,
    ) -> Request:
        if (extras is None) != (self.extras_template is None):
            raise ValueError(
                "request extras must match the engine's extras_template"
            )
        return self.scheduler.submit(
            prompt,
            max_new_tokens,
            arrival=self.clock() if arrival is None else arrival,
            extras=extras,
        )

    def evict(self, rid: int) -> Request:
        req = self.scheduler.evict(rid, now=self.clock())
        self._sync_active()
        return req

    def outstanding_tokens(self) -> int:
        return self.scheduler.outstanding_tokens()

    @property
    def idle(self) -> bool:
        return self.scheduler.idle

    def _sync_active(self):
        mask = np.zeros((self.padded_slots,), bool)
        mask[: self.scheduler.num_slots] = self.scheduler.active_mask()
        self._active = jnp.asarray(mask)

    # -- the decode-step boundary -------------------------------------------

    def step(self, *, now: float | None = None) -> list[Request]:
        """One continuous-batching boundary: admit into free slots
        (B=1 bucketed prefill, scattered into slot rows), run one decode
        slice, record the emitted tokens.  Returns requests that
        *finished* during this step.  No-op (returns ``[]``) when idle.

        Host spans: ``serve.admit`` (holding a ``serve.prefill`` with
        counts ``bucket`` and ``prompt`` and the ``mode``, ``"parallel"``
        or ``"stepwise"``, per admission), ``serve.slice``
        (the dispatch), ``serve.wait`` (until the slice's tokens are on
        the host) and ``serve.record`` (counts ``steps`` and ``tokens``).
        """
        now = self.clock() if now is None else now
        with span("serve.admit"):
            for req in self.scheduler.admit(now=now):
                with span(
                    "serve.prefill",
                    bucket=req.bucket_len,
                    prompt=len(req.prompt),
                    mode=self._prefill_mode,
                ):
                    cache_b1, tok0 = self._prefill(req)
                self.prefills[self._prefill_mode] += 1
                row = jnp.asarray(req.slot, jnp.int32)
                self._cache = self._scatter(self._cache, cache_b1, row)
                self._tok, self._active = self._set_tok(
                    self._tok, self._active, row, tok0[0]
                )
            self._sync_active()
        if not any(self.scheduler.active_mask()):
            return []

        t0 = self.clock()
        with span("serve.slice"):
            out, self._tok, self._cache, steps = self._slice(
                self.params, self._cache, self._tok, self._active
            )
        with span("serve.wait"):
            out = np.asarray(out)
            steps_run = int(steps)
        t1 = self.clock()

        finished: list[Request] = []
        with span("serve.record", steps=steps_run) as record:
            tokens = 0
            for t in range(steps_run):
                for slot in range(self.scheduler.num_slots):
                    # record_token drops tokens for freed/never-filled
                    # slots, so garbage rows and post-EOS columns are
                    # no-ops
                    tokens += self.scheduler.slots[slot] is not None
                    done = self.scheduler.record_token(
                        slot, int(out[slot, t]), now=t1
                    )
                    if done is not None:
                        finished.append(done)
            self._sync_active()
            record.set_metadata(tokens=tokens)

        # MachineParams.fit-shaped measurement row for the logits
        # allreduce this slice ran: (nbytes, seconds-per-step, senders).
        # Effective single-message rows: senders=1 (whole-payload time).
        if steps_run:
            nbytes = (
                self.group * self.b_max * self.model.cfg.vocab_size * 4
            )
            self.step_times.append(
                (int(nbytes), (t1 - t0) / steps_run, 1)
            )
            self.n_slices += 1
            self.n_decode_steps += steps_run
        return finished

    def run(self, *, max_steps: int = 100_000) -> dict[int, list[int]]:
        """Drive :meth:`step` until idle; returns ``rid -> tokens`` for
        every request that reached a terminal state."""
        for _ in range(max_steps):
            if self.idle:
                break
            self.step()
        else:
            raise RuntimeError(f"not idle after {max_steps} engine steps")
        return {
            rid: list(req.generated)
            for rid, req in self.scheduler.requests.items()
            if req.done
        }

    # -- introspection -------------------------------------------------------

    def dispatch_report(self) -> dict[str, dict]:
        """The (engine, chunks) decision for each decode-step collective
        at this engine's payload sizes — the per-collective dispatch
        table BENCH_9 publishes."""
        if self.ctx is None:
            return {}
        topo = self.ctx.topology
        V = self.model.cfg.vocab_size
        D = self.model.cfg.d_model
        d_cols = -(-D // max(self.group, 1))
        rows = self.group * self.b_max
        payloads = {
            "logits_allreduce": (rows * V * 4, "sum", "allreduce", None),
            "hidden_allgather": (
                rows * d_cols * self.group * 4,
                "sum",
                "allgather",
                "mla_ag" if topo.has_slow_domain else None,
            ),
            "eos_min_reduce": (4, "min", "allreduce", "psum"),
        }
        report = {}
        for name, (nbytes, op, coll, pin) in payloads.items():
            d = self.ctx.dispatch(
                int(nbytes), op, collective=coll, algorithm=pin
            )
            report[name] = {
                "nbytes": int(nbytes),
                "op": op,
                "collective": coll,
                "engine": d.engine,
                "pipeline_chunks": d.chunks,
            }
        return report

    def fit_rows(self) -> list[tuple[int, float, int]]:
        """Per-decode-step wall-clock as ``MachineParams.fit`` rows
        ``(size_bytes, seconds, senders)`` (open item 4's serving
        data)."""
        return list(self.step_times)
