"""Serving-spine tests: scheduler invariants, buckets, router, engine.

The scheduler is pure host-side Python, so its invariants are fuzzed
directly over request arrival traces (hypothesis when available, the
deterministic ``_hypothesis_compat`` sweep otherwise):

* no slot leaks — free + active always partitions the slot range;
* FIFO fairness under saturation — admission order is arrival order;
* silence after the end — finished/evicted/rejected requests never
  gain another token.

The engine-level check (single device) asserts continuous batching is
**bitwise identical** to the fixed-batch serial driver
(:func:`repro.launch.serve.serve_batch`), including through padded
prompt buckets and mid-flight admission.  The multidevice (meshed,
tensor-parallel) version of the same property lives in
``_multidevice_checks.py::check_serve_continuous_batching``.
"""

import random

import pytest
from _hypothesis_compat import given, settings, st

from repro.serve.scheduler import (
    ACTIVE,
    EVICTED,
    FINISHED,
    QUEUED,
    REJECTED,
    PromptBuckets,
    Scheduler,
)

# ---------------------------------------------------------------------------
# PromptBuckets


def test_bucket_len_picks_smallest_holding_bucket():
    b = PromptBuckets([16, 4, 8, 8])  # dedup + sort
    assert b.lengths == (4, 8, 16)
    assert b.bucket_len(1) == 4
    assert b.bucket_len(4) == 4
    assert b.bucket_len(5) == 8
    assert b.bucket_len(16) == 16
    with pytest.raises(ValueError):
        b.bucket_len(17)


def test_bucket_validation():
    with pytest.raises(ValueError):
        PromptBuckets([])
    with pytest.raises(ValueError):
        PromptBuckets([0, 8])
    with pytest.raises(ValueError):
        PromptBuckets.geometric(64, factor=1)


def test_geometric_ladder_covers_max_len():
    b = PromptBuckets.geometric(100, start=8, factor=2)
    assert b.lengths == (8, 16, 32, 64, 100)
    assert b.max_len == 100
    for n in range(1, 101):
        assert b.bucket_len(n) >= n
    # trace count is logarithmic, not linear
    assert len(b.lengths) <= 8


# ---------------------------------------------------------------------------
# Scheduler: directed unit tests


def test_fifo_admission_under_saturation():
    s = Scheduler(2)
    reqs = [s.submit([1], 1) for _ in range(5)]
    admitted = s.admit()
    assert [r.rid for r in admitted] == [reqs[0].rid, reqs[1].rid]
    assert [r.slot for r in admitted] == [0, 1]
    # finishing one request admits exactly the queue head into its slot
    for nxt in (2, 3, 4):
        done = s.record_token(0, 7)
        assert done is not None and done.state == FINISHED
        newly = s.admit()
        assert [r.rid for r in newly] == [reqs[nxt].rid]
        assert newly[0].slot == 0
        s.check_invariants()


def test_admission_control_rejects_past_queue_bound():
    s = Scheduler(1, max_queue=2)
    ok = [s.submit([1], 1) for _ in range(2)]
    bad = s.submit([1], 1)
    assert all(r.state == QUEUED for r in ok)
    assert bad.state == REJECTED and bad.remaining == 0
    assert s.n_rejected == 1
    # rejected requests never enter the queue or a slot
    s.admit()
    assert bad.slot is None
    s.check_invariants()


def test_eos_and_budget_finish():
    s = Scheduler(1, eos_id=99)
    r1 = s.submit([1], 4)
    s.admit()
    assert s.record_token(0, 5) is None
    assert s.record_token(0, 99) is r1  # EOS beats remaining budget
    assert r1.generated == [5, 99] and r1.state == FINISHED
    r2 = s.submit([1], 2)
    s.admit()
    s.record_token(0, 1)
    assert s.record_token(0, 2) is r2  # budget exhaustion
    assert r2.generated == [1, 2]


def test_tokens_for_free_slots_are_dropped():
    s = Scheduler(2)
    s.submit([1], 3)
    s.admit()
    # slot 1 was never filled; the engine decodes it unconditionally
    assert s.record_token(1, 123) is None
    s.check_invariants()


def test_evicted_requests_never_emit_tokens():
    s = Scheduler(1)
    r1 = s.submit([1], 5)
    r2 = s.submit([2], 5)
    s.admit()
    s.record_token(0, 11)
    s.evict(r1.rid)
    assert r1.state == EVICTED and r1.slot is None
    n_before = len(r1.generated)
    # the token the engine already computed for the freed slot is dropped
    assert s.record_token(0, 12) is None
    assert len(r1.generated) == n_before
    # eviction of a queued request removes it before it ever runs
    s.evict(r2.rid)
    assert r2.state == EVICTED and r2.generated == []
    assert s.admit() == [] and s.idle
    # terminal evict is a no-op
    assert s.evict(r1.rid) is r1
    s.check_invariants()


def test_outstanding_tokens_counts_queue_and_slots():
    s = Scheduler(1)
    r1 = s.submit([1], 5)
    s.submit([2], 3)
    assert s.outstanding_tokens() == 8
    s.admit()
    s.record_token(0, 1)
    assert s.outstanding_tokens() == 7
    s.evict(r1.rid)
    assert s.outstanding_tokens() == 3


def test_shard_geometry_is_ragged_splits():
    from repro.core import napalg

    s = Scheduler(10)
    for group in (1, 2, 3, 4, 8):
        geo = s.shard_geometry(group)
        assert geo == napalg.ragged_splits(10, group)
        assert sum(geo) == 10 and len(geo) == group


def test_request_validation():
    with pytest.raises(ValueError):
        Scheduler(0)
    s = Scheduler(1)
    with pytest.raises(ValueError):
        s.submit([], 1)
    with pytest.raises(ValueError):
        s.submit([1], 0)


# ---------------------------------------------------------------------------
# Scheduler: fuzz over arrival traces


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10**6),
    num_slots=st.integers(min_value=1, max_value=4),
    max_queue=st.one_of(st.none(), st.integers(min_value=0, max_value=3)),
)
def test_scheduler_trace_fuzz(seed, num_slots, max_queue):
    rng = random.Random(seed)
    eos = 99 if rng.random() < 0.5 else None
    s = Scheduler(num_slots, max_queue=max_queue, eos_id=eos)
    submitted = []          # arrival order
    admitted_order = []     # admission order
    frozen = {}             # rid -> generated length at terminal transition

    def note_terminals():
        for req in s.requests.values():
            if req.done:
                frozen.setdefault(req.rid, len(req.generated))
                # silence after the end: a terminal request's token list
                # must never grow again
                assert len(req.generated) == frozen[req.rid], req
                assert req.slot is None
                assert req.remaining == 0

    for _ in range(80):
        op = rng.random()
        if op < 0.35:
            req = s.submit(
                [rng.randrange(100) + 1 for _ in range(rng.randrange(1, 5))],
                rng.randrange(1, 4),
            )
            if req.state != REJECTED:
                submitted.append(req.rid)
        elif op < 0.55:
            # FIFO: admit() must take exactly the current queue head(s)
            expect = [r.rid for r in list(s.queue)[: len(s.free_slots)]]
            got = [r.rid for r in s.admit()]
            assert got == expect
            admitted_order.extend(got)
        elif op < 0.85:
            # one decode step: the engine records a token for EVERY slot
            for slot in range(num_slots):
                s.record_token(slot, rng.choice([99, rng.randrange(98)]))
        else:
            live = [
                r.rid for r in s.requests.values() if not r.done
            ]
            if live:
                s.evict(rng.choice(live))
        s.check_invariants()
        note_terminals()

    # FIFO fairness: admissions happen in arrival order (eviction from
    # the queue only removes entries; it never reorders survivors)
    pos = {rid: i for i, rid in enumerate(submitted)}
    order = [pos[rid] for rid in admitted_order]
    assert order == sorted(order)
    # no slot leak survives the whole trace
    assert len(s.free_slots) + len(s.active()) == num_slots
    # every admitted request was actually submitted (never rejected)
    assert set(admitted_order) <= set(submitted)


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10**6),
    num_slots=st.integers(min_value=1, max_value=3),
)
def test_scheduler_drains_to_idle(seed, num_slots):
    """Any backlog drains to idle under admit+decode steps alone."""
    rng = random.Random(seed)
    s = Scheduler(num_slots)
    reqs = [
        s.submit([1 + rng.randrange(9)], rng.randrange(1, 5))
        for _ in range(rng.randrange(1, 9))
    ]
    steps = 0
    while not s.idle:
        s.admit()
        for slot in range(num_slots):
            s.record_token(slot, rng.randrange(50))
        s.check_invariants()
        steps += 1
        assert steps < 1000, "scheduler failed to drain"
    for r in reqs:
        assert r.state == FINISHED
        assert len(r.generated) == r.max_new_tokens


# ---------------------------------------------------------------------------
# Router + replica health


class _FakeReplica:
    """Minimal replica surface the Router needs (no device state)."""

    def __init__(self, num_slots, **kw):
        self.scheduler = Scheduler(num_slots, **kw)

    def submit(self, prompt, max_new_tokens, **kw):
        return self.scheduler.submit(prompt, max_new_tokens, **kw)

    def outstanding_tokens(self):
        return self.scheduler.outstanding_tokens()

    @property
    def idle(self):
        return self.scheduler.idle


def test_router_spreads_by_outstanding_tokens():
    from repro.serve import Router

    r = Router([_FakeReplica(2), _FakeReplica(2)])
    big = r.submit([1], 100)        # -> replica 0 (tie, lowest index)
    small = r.submit([1], 1)        # -> replica 1 (less loaded)
    nxt = r.submit([1], 1)          # -> replica 1 again (2 < 100)
    assert r.placement[big.rid] == 0
    assert r.placement[small.rid] == 1
    assert r.placement[nxt.rid] == 1
    assert r.loads() == [100, 2]


def test_router_rejected_requests_are_not_placed():
    from repro.serve import Router

    r = Router([_FakeReplica(1, max_queue=0)])
    req = r.submit([1], 1)
    assert req.state == REJECTED
    assert req.rid not in r.placement


def test_replica_health_hysteresis():
    from repro.runtime.fault import ReplicaHealth, StragglerMonitor

    h = ReplicaHealth(
        StragglerMonitor(threshold=2.0, warmup=3), recovery=3
    )
    for step in range(4):
        assert h.record(step, 1.0)
    assert not h.record(4, 10.0)        # straggler event -> degraded
    assert h.n_degraded == 1
    assert not h.record(5, 1.0)         # one clean step is not recovery
    assert not h.record(6, 1.0)
    assert h.record(7, 1.0)             # 3 consecutive clean -> healthy
    # a new event restarts the clean counter
    assert not h.record(8, 50.0)
    assert not h.record(9, 1.0)
    assert h.n_degraded == 2


def test_router_reroutes_queue_on_straggler():
    from repro.serve import Router

    a, b = _FakeReplica(1), _FakeReplica(1)
    r = Router([a, b], straggler_threshold=2.0, recovery=2)
    # saturate replica 0 and build its queue (directly: the router
    # itself would spread this backlog to the emptier replica 1)
    first = r.submit([1], 50)
    a.scheduler.admit()
    queued = [a.submit([1], 50) for _ in range(3)]
    # straggler signal on replica 0 past monitor warmup
    for step in range(4):
        assert r.observe_step(0, step, 1.0)
    assert not r.observe_step(0, 4, 25.0)
    # queued requests moved to the healthy peer; the active one stayed
    assert not r.health[0].healthy
    assert a.scheduler.queue == type(a.scheduler.queue)()
    assert first.state == ACTIVE and r.placement[first.rid] == 0
    moved = [q for q in queued if q.state == QUEUED]
    assert moved and all(r.placement[q.rid] == 1 for q in moved)
    assert r.n_rerouted == len(moved)
    # while degraded, new submissions avoid replica 0
    assert r.placement[r.submit([1], 1).rid] == 1
    # recovery hysteresis readmits it
    r.observe_step(0, 5, 1.0)
    r.observe_step(0, 6, 1.0)
    assert r.health[0].healthy


def test_router_all_degraded_still_routes():
    from repro.serve import Router
    from repro.runtime.fault import ReplicaHealth, StragglerMonitor

    h = [
        ReplicaHealth(StragglerMonitor(warmup=1), recovery=2)
        for _ in range(2)
    ]
    r = Router([_FakeReplica(1), _FakeReplica(1)], health=h)
    for i in (0, 1):
        r.observe_step(i, 0, 1.0)
        r.observe_step(i, 1, 1.0)
        r.observe_step(i, 2, 100.0)
    assert not any(x.healthy for x in r.health)
    req = r.submit([1], 1)  # stalled beats dropped
    assert req.state == QUEUED and req.rid in r.placement


def test_evict_after_reroute_goes_through_single_owner():
    # layer-0 counterexample (submit, degrade, evict-via-stale-owner):
    # before single ownership, the drained rid stayed in the source
    # registry and evicting through it crashed in deque.remove
    from repro.serve import Router

    a, b = _FakeReplica(1), _FakeReplica(1)
    r = Router([a, b], straggler_threshold=2.0, recovery=2)
    first = r.submit([1], 50)
    a.scheduler.admit()
    q = a.submit([1], 5)
    for step in range(4):
        assert r.observe_step(0, step, 1.0)
    assert not r.observe_step(0, 4, 25.0)  # degrade -> reroute
    # ownership moved with the request: exactly one registry owns it
    assert q.rid not in a.scheduler.requests
    assert q.rid in b.scheduler.requests
    with pytest.raises(KeyError):
        a.scheduler.evict(q.rid)
    # the router's placement stayed accurate, so evicting through it
    # reaches the real owner
    r.evict(q.rid)
    assert q.state == EVICTED
    assert first.state == ACTIVE  # the active request rode out the stall
    a.scheduler.check_invariants(peers=[b.scheduler])


def test_reroute_keeps_accepted_request_when_no_peer_has_room():
    # layer-0 counterexample (submit, submit, degrade): before the
    # capacity-aware reroute, draining into a full peer queue flipped
    # an accepted request to REJECTED mid-flight
    from repro.runtime.fault import ReplicaHealth, StragglerMonitor
    from repro.serve import Router

    a = _FakeReplica(1, max_queue=1)
    b = _FakeReplica(1, max_queue=1)
    h = [
        ReplicaHealth(
            StragglerMonitor(threshold=2.0, warmup=1), recovery=2
        )
        for _ in range(2)
    ]
    r = Router([a, b], health=h)
    for i in (0, 1):
        r.observe_step(i, 0, 1.0)
        r.observe_step(i, 1, 1.0)
    qa = r.submit([1], 5)     # -> replica 0 (tie, lowest index)
    qb = r.submit([1], 5)     # -> replica 1; both queues now full
    assert not r.observe_step(0, 2, 25.0)  # degrade 0 -> reroute
    # acceptance is binding: no room on the peer, so the request stays
    # queued (FIFO position intact) on the degraded replica
    assert qa.state == QUEUED and r.placement[qa.rid] == 0
    assert list(a.scheduler.queue) == [qa]
    assert qb.state == QUEUED and r.placement[qb.rid] == 1
    a.scheduler.check_invariants(peers=[b.scheduler])


def test_pick_prefers_replica_with_queue_capacity():
    from repro.serve import Router

    a = _FakeReplica(1, max_queue=1)
    b = _FakeReplica(1)
    r = Router([a, b])
    big = b.scheduler.submit([1], 100)
    b.scheduler.admit()           # replica 1 heavily loaded but roomy
    a.scheduler.submit([1], 1)    # replica 0 light but queue full
    req = r.submit([1], 1)
    # least-loaded would pick the full replica 0 and reject; capacity
    # preference routes to the loaded-but-roomy replica 1 instead
    assert req.state == QUEUED
    assert r.placement[req.rid] == 1
    assert big.state == ACTIVE


def test_fail_replica_replans_queued_and_active():
    from repro.serve import Router

    a = _FakeReplica(2, max_queue=1)
    b = _FakeReplica(1, max_queue=1)
    r = Router([a, b])
    act = r.submit([1], 10)       # -> replica 0 (tie, lowest index)
    a.scheduler.admit()
    q1 = a.submit([1], 5)         # queued on replica 0 (queue full)
    b_q = r.submit([1], 3)        # -> replica 1 (less loaded)
    moved = r.fail_replica(0)
    assert moved == 2 and 0 in r.failed
    # the dead replica is empty — its work drained into the re-plan
    assert a.scheduler.idle and not a.scheduler.requests
    # the active request lost its KV state: demoted to QUEUED, slot
    # released, generated tokens kept for the re-prefill
    assert act.state == QUEUED and act.slot is None
    # survivors keep FIFO order: b's own head, then the demoted
    # active (admitted first), then the queued mover — force-enqueued
    # past b's backpressure bound rather than dropped
    assert [x.rid for x in b.scheduler.queue] == [b_q.rid, act.rid, q1.rid]
    assert r.placement[act.rid] == 1 and r.placement[q1.rid] == 1
    # a dead replica never receives traffic again: with the survivor
    # over its bound the submit is REJECTED (honest backpressure),
    # never routed to the corpse
    rejected = r.submit([1], 1)
    assert rejected.state == REJECTED and rejected.rid not in r.placement
    while not b.scheduler.idle:  # drain the survivor
        b.scheduler.admit()
        b.scheduler.record_token(0, 1)
    assert r.placement[r.submit([1], 1).rid] == 1
    b.scheduler.check_invariants(peers=[a.scheduler])
    with pytest.raises(RuntimeError):
        r.fail_replica(1)  # no survivor to re-plan onto


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_router_trace_fuzz_cross_replica_conservation(seed):
    """Random multi-replica traces (submit/admit/decode/health/evict/
    loss) hold the cross-replica conservation invariants at every step:
    global rid uniqueness and outstanding-token accounting."""
    from repro.runtime.fault import ReplicaHealth, StragglerMonitor
    from repro.serve import Router

    rng = random.Random(seed)
    n = rng.choice([2, 3])
    reps = [
        _FakeReplica(
            rng.randrange(1, 3),
            max_queue=rng.choice([None, 1, 2]),
            eos_id=99,
        )
        for _ in range(n)
    ]
    health = [
        ReplicaHealth(
            StragglerMonitor(threshold=2.0, warmup=1, alpha=0.5),
            recovery=2,
        )
        for _ in range(n)
    ]
    r = Router(reps, health=health)
    step = 0
    for i in range(n):
        for _ in range(2):
            r.observe_step(i, step, 1.0)
            step += 1
    for _ in range(120):
        op = rng.random()
        alive = [i for i in range(n) if i not in r.failed]
        if op < 0.30:
            r.submit([1 + rng.randrange(9)], rng.randrange(1, 4))
        elif op < 0.45:
            reps[rng.choice(alive)].scheduler.admit()
        elif op < 0.70:
            i = rng.choice(alive)
            for slot in range(reps[i].scheduler.num_slots):
                reps[i].scheduler.record_token(
                    slot, rng.choice([99, 1 + rng.randrange(9)])
                )
        elif op < 0.80:
            r.observe_step(
                rng.choice(alive), step, rng.choice([1.0, 25.0])
            )
            step += 1
        elif op < 0.92:
            live = [
                rid
                for i in alive
                for rid, req in reps[i].scheduler.requests.items()
                if not req.done
            ]
            if live:
                r.evict(rng.choice(live))
        elif len(alive) >= 2:
            r.fail_replica(rng.choice(alive))
        # cross-replica conservation after every operation
        for i, rep in enumerate(reps):
            rep.scheduler.check_invariants(
                peers=[x.scheduler for j, x in enumerate(reps) if j != i]
            )


# ---------------------------------------------------------------------------
# Engine (single device): continuous batching == serial fixed batch


@pytest.mark.parametrize("arch", ["minicpm-2b"])
def test_engine_bitwise_matches_serial_serve_batch(arch):
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.launch.serve import serve_batch
    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))

    prompts = np.array([[3, 1, 4], [1, 5, 9], [2, 6, 5]], np.int32)
    gen = 5

    # serial reference: every request decoded in one fixed batch
    ref = np.asarray(
        serve_batch(
            model, params, jax.numpy.asarray(prompts),
            gen_len=gen, max_len=16,
        )
    )

    # continuous batching: 2 slots for 3 requests, the third joins a
    # slot freed in flight; prompts ride a padded bucket (3 -> 8)
    engine = ServeEngine(
        model, params, num_slots=2, max_len=16,
        buckets=PromptBuckets([8]),
    )
    reqs = [
        engine.submit(list(p), b)
        for p, b in zip(prompts, (gen, gen - 2, gen))
    ]
    out = engine.run()
    assert engine.idle
    for i, req in enumerate(reqs):
        want = ref[i, : req.max_new_tokens].tolist()
        assert out[req.rid] == want, (i, out[req.rid], want)
    # per-decode-step fit rows were recorded with the logits payload
    # (all b_max slot rows ride one allreduce, f32)
    rows = engine.fit_rows()
    want_bytes = engine.b_max * cfg.vocab_size * 4
    assert rows and all(
        n == want_bytes and t > 0 and k == 1 for (n, t, k) in rows
    )


def test_engine_steps_the_prompt_where_the_model_has_no_parallel_prefill():
    # a recurrent state has no parallel form here: the engine keeps the
    # token loop, whose tokens equal the fixed-batch serial driver's
    import jax
    import numpy as np

    from repro.configs import get_config, reduced
    from repro.launch.serve import serve_batch
    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    model = build_model(reduced(get_config("rwkv6-1.6b")))
    assert model.prefill is None
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    prompts = np.array([[3, 1, 4], [1, 5, 9], [2, 6, 5]], np.int32)
    gen = 5
    ref = np.asarray(
        serve_batch(
            model, params, jax.numpy.asarray(prompts),
            gen_len=gen, max_len=16,
        )
    )
    engine = ServeEngine(
        model, params, num_slots=2, max_len=16,
        buckets=PromptBuckets([8]),
    )
    reqs = [engine.submit(list(p), gen) for p in prompts]
    out = engine.run()
    assert [out[r.rid] for r in reqs] == ref.tolist()
    assert engine.prefills == {"stepwise": len(prompts)}


def test_engine_eos_early_finish():
    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    cfg = reduced(get_config("minicpm-2b"))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))

    # discover the greedy continuation, then declare its 3rd token EOS
    probe = ServeEngine(
        model, params, num_slots=1, max_len=16, buckets=PromptBuckets([4])
    )
    free_run = probe.run_one = probe.submit([3, 1, 4], 5)
    toks = probe.run()[free_run.rid]
    eos = toks[2]
    if toks.index(eos) != 2:  # eos token appeared earlier: shift target
        eos = toks[toks.index(eos)]

    engine = ServeEngine(
        model, params, num_slots=1, max_len=16,
        buckets=PromptBuckets([4]), eos_id=eos,
    )
    req = engine.submit([3, 1, 4], 5)
    out = engine.run()
    assert out[req.rid] == toks[: toks.index(eos) + 1]
    assert req.state == FINISHED and engine.idle


def test_engine_extras_template_is_enforced():
    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import ServeEngine

    cfg = reduced(get_config("minicpm-2b"))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, num_slots=1, max_len=8)
    with pytest.raises(ValueError):
        engine.submit([1], 1, extras={"frames": None})


def test_dropped_engine_frees_its_device_state_without_gc():
    # the engine's compiled programs must not close over the engine: in
    # a reference cycle its params and cache stay on the device until
    # the cyclic collector runs (a one-chip run that serves and then
    # trains ran out of device memory that way)
    import gc
    import weakref

    import jax

    from repro.configs import get_config, reduced
    from repro.models import build_model
    from repro.serve import PromptBuckets, ServeEngine

    cfg = reduced(get_config("minicpm-2b"))
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = ServeEngine(
        model, params, num_slots=2, max_len=16, buckets=PromptBuckets([4])
    )
    engine.submit([3, 1, 4], 3)
    engine.run()
    refs = [weakref.ref(engine)] + [
        weakref.ref(x) for x in jax.tree.leaves(engine._cache)
    ]
    gc.disable()
    try:
        del engine
        assert all(r() is None for r in refs)
    finally:
        gc.enable()
