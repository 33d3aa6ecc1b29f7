"""Paged-KV serve engine: page lifecycle, admission policy, chunked
prefill, preemption/resume, and the PR-10 bugfix regressions.

Stub model: logits are a pure function of the *input token and its
position* (``next == (7*t + 3 + 2*pos) % vocab``), so slot mixups,
position drift after a resume, and chunked-prefill indexing errors all
change visible tokens instead of hiding in argmax-of-ones.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.serve.engine import Request, ServeEngine
from repro.serve.kv import PagedKV
from repro.serve.scheduler import Scheduler, SchedulerConfig

VOCAB = 32


def _f(t, p):
    return (7 * t + 3 + 2 * p) % VOCAB


def _chain(seq, max_new):
    """Reference greedy chain for :class:`_CountModel`."""
    L = len(seq)
    out = [_f(int(seq[-1]), L - 1)]
    while len(out) < max_new:
        out.append(_f(out[-1], L + len(out) - 1))
    return out


class _CountModel:
    """Next-token logits = one-hot of ``_f(input token, position)``.

    Position-dependence makes chunked prefill and preemption-resume
    *observable*: a lane resumed at the wrong position, or a streamed
    prompt fed at a shifted index, produces a different token chain.
    """

    def __init__(self, vocab=VOCAB, d=8):
        self.vocab = vocab
        rng = np.random.default_rng(0)
        self.embed = rng.normal(size=(vocab, d)).astype(np.float32)

    def init_cache(self, b, cap):
        return {"n": jnp.zeros((b,), jnp.int32)}

    def _embed(self, params, tokens):
        return jnp.asarray(self.embed)[tokens]

    def prefill(self, params, tokens, capacity=None):
        b, s = tokens.shape
        posn = jnp.arange(s, dtype=jnp.int32)[None, :]
        logits = jax.nn.one_hot((7 * tokens + 3 + 2 * posn) % self.vocab,
                                self.vocab)
        return logits, {"n": jnp.full((b,), s, jnp.int32)}

    def decode_step(self, params, caches, tokens, pos):
        logits = jax.nn.one_hot(
            (7 * tokens + 3 + 2 * pos[:, None]) % self.vocab, self.vocab)
        return logits, caches


def _engine(**kw):
    return ServeEngine(_CountModel(), params={},
                       batch_slots=kw.pop("B", 2),
                       capacity=kw.pop("capacity", 32), **kw)


def _req(rid, plen, max_new=2, **kw):
    prompt = ((np.arange(plen) * 5 + rid) % VOCAB).astype(np.int32)
    return Request(rid=rid, prompt=prompt, max_new=max_new, **kw)


# ---------------------------------------------------------------------------
# PagedKV unit behaviour
# ---------------------------------------------------------------------------
def test_kv_pages_for_and_capacity():
    kv = PagedKV(num_pages=4, page_size=4)
    assert [kv.pages_for(n) for n in (1, 4, 5, 8, 9)] == [1, 1, 2, 2, 3]
    assert kv.capacity_tokens == 16
    assert kv.can_ever_fit(16) and not kv.can_ever_fit(17)


def test_kv_alloc_append_free_lifecycle():
    kv = PagedKV(num_pages=3, page_size=4)
    assert kv.alloc(0, 4)                     # 1 page
    assert kv.free_pages == 2
    assert kv.append(0)                       # 5 tokens -> 2 pages
    assert kv.used_pages == 2
    assert kv.alloc(1, 3)                     # 3rd page
    assert kv.free_pages == 0
    assert kv.append(1)                       # 4 tokens: same page
    assert kv.used_pages == 3
    assert not kv.append(1)                   # 5 tokens: pool dry
    assert kv.lens[1] == 4                    # failed append changed nothing
    assert kv.stats["failed_appends"] == 1
    assert kv.free(0) == 2
    assert kv.append(1)                       # retries succeed after free
    kv.free(1)
    kv.assert_empty()
    assert kv.stats["allocs"] == kv.stats["frees"] == 2


def test_kv_failed_alloc_leaves_state_clean():
    kv = PagedKV(num_pages=2, page_size=4)
    assert kv.alloc(7, 8)                     # both pages
    assert not kv.alloc(8, 1)
    assert 8 not in kv.tables and kv.free_pages == 0
    with pytest.raises(KeyError):
        kv.alloc(7, 1)                        # double admit is a bug
    kv.free(7)
    kv.assert_empty()


def test_kv_leak_is_loud():
    kv = PagedKV(num_pages=2, page_size=4)
    kv.alloc(3, 4)
    with pytest.raises(AssertionError, match="leaked"):
        kv.assert_empty()


# ---------------------------------------------------------------------------
# Bugfix regression 1: long prompts reject instead of crashing
# ---------------------------------------------------------------------------
def test_prompt_longer_than_capacity_is_rejected_not_crashed():
    """The old engine raised a numpy broadcast ValueError at
    ``padded[:plen]`` for plen = capacity + 1."""
    eng = _engine(B=1, capacity=16)
    eng.add(_req(0, plen=17, max_new=1))      # capacity + 1
    done = eng.run()                          # must not raise
    assert done == []
    assert eng.stats["rejected"] == 1
    assert len(eng.rejected) == 1
    assert eng.rejected[0].status == "rejected"
    assert eng.rejected[0].out == []
    eng.kv.assert_empty()


def test_prompt_plus_budget_beyond_capacity_is_rejected():
    """Admission is strict: prompt + max_new must fit the slot (no
    silent ring wraparound)."""
    eng = _engine(B=1, capacity=16)
    eng.add(_req(0, plen=12, max_new=8))      # 20 > 16
    assert eng.run() == [] and eng.stats["rejected"] == 1
    # the boundary case fits
    eng.add(_req(1, plen=12, max_new=4))      # 16 == 16
    done = eng.run()
    assert len(done) == 1 and len(done[0].out) == 4


def test_rejected_requests_do_not_block_the_queue():
    eng = _engine(B=1, capacity=16)
    eng.add(_req(0, plen=17, max_new=1))
    eng.add(_req(1, plen=4, max_new=2))
    done = eng.run()
    assert [r.rid for r in done] == [1]
    assert done[0].out == _chain(done[0].prompt, 2)
    assert eng.stats["rejected"] == 1


def test_long_prompt_truncate_policy():
    eng = _engine(B=1, capacity=16, long_prompt="truncate")
    req = _req(0, plen=20, max_new=2)
    full_prompt = req.prompt.copy()
    eng.add(req)
    done = eng.run()
    assert len(done) == 1 and done[0].truncated
    assert eng.stats["truncated"] == 1 and eng.stats["rejected"] == 0
    limit = 16 - 2                            # capacity - max_new
    assert len(done[0].prompt) == limit
    assert done[0].out == _chain(full_prompt[:limit], 2)


# ---------------------------------------------------------------------------
# Bugfix regression 2: freed slots backfill in the same step
# ---------------------------------------------------------------------------
def test_freed_slot_serves_in_the_same_step():
    """With a full queue, the active-slot count never drops while work
    remains: retirement backfills before the step returns."""
    eng = _engine(B=2)
    for rid in range(6):
        eng.add(_req(rid, plen=2, max_new=2))
    while eng.queue or any(s is not None for s in eng.slots):
        eng.step()
        work_remains = bool(eng.queue)
        active = sum(1 for s in eng.slots if s is not None)
        if work_remains:
            assert active == eng.B, \
                f"slot sat idle with {len(eng.queue)} queued"
    assert eng.stats["admitted"] == 6


def test_backfill_keeps_fifo_order_and_chains():
    eng = _engine(B=2)
    for rid in range(5):
        eng.add(_req(rid, plen=3, max_new=2))
    done = eng.run()
    assert [r.rid for r in done] == [0, 1, 2, 3, 4]
    for r in done:
        assert r.out == _chain(r.prompt, 2)


# ---------------------------------------------------------------------------
# Bugfix regression 3: unified accounting epilogue
# ---------------------------------------------------------------------------
def test_admit_only_step_counts_accounting():
    """A step whose only work was a prefill (max_new=1: no decode ever
    runs) still counts the step, advances the sampling counter, and
    checks the deadline -- the old early return skipped all three."""
    eng = _engine(B=1, step_deadline_ms=0.0)
    eng.add(_req(0, plen=4, max_new=1))
    done = eng.step()
    assert len(done) == 1 and done[0].out == _chain(done[0].prompt, 1)
    assert eng.stats["steps"] == 1
    assert eng.stats["deadline_misses"] == 1
    assert eng._step_count == 1


def test_idle_step_still_counts_nothing():
    eng = _engine(B=1)
    assert eng.step() == []
    assert eng.stats["steps"] == 0 and eng._step_count == 0


# ---------------------------------------------------------------------------
# Bugfix regression 3b: the probe sees only ACTIVE lanes
# ---------------------------------------------------------------------------
class _ShapeProbe:
    done = False
    faults = None
    escaped_outputs = 0

    def __init__(self):
        self.shapes = []

    def observe(self, x):
        self.shapes.append(tuple(np.asarray(x).shape))

    def observe_ref(self, x):                 # pragma: no cover
        return None

    def report(self):                         # pragma: no cover
        return {}


def test_probe_observes_only_active_lanes():
    """B=4 engine with 2 requests: the probe must see M=2 activations,
    never the stale token embeddings of the 2 empty slots."""
    probe = _ShapeProbe()
    eng = _engine(B=4, fabric_probe=probe)
    eng.add(_req(0, plen=2, max_new=3))
    eng.add(_req(1, plen=2, max_new=3))
    eng.run()
    assert probe.shapes, "probe never observed"
    assert all(s[0] == 2 for s in probe.shapes), probe.shapes


def test_probe_lane_count_tracks_retirement():
    """As requests finish, the observed M shrinks with the live batch."""
    probe = _ShapeProbe()
    eng = _engine(B=2, fabric_probe=probe)
    eng.add(_req(0, plen=2, max_new=4))
    eng.add(_req(1, plen=2, max_new=2))
    eng.run()
    ms = [s[0] for s in probe.shapes]
    assert ms[0] == 2 and ms[-1] == 1         # r1 retires first


# ---------------------------------------------------------------------------
# Page lifecycle through the engine
# ---------------------------------------------------------------------------
def test_no_leaked_pages_after_run():
    eng = _engine(B=2, capacity=16, page_size=4)
    for rid in range(7):
        eng.add(_req(rid, plen=3 + rid % 5, max_new=1 + rid % 3))
    done = eng.run()
    assert len(done) == 7
    eng.kv.assert_empty()
    rep = eng.kv_report()
    assert rep["allocs"] == rep["frees"] == 7
    assert rep["pages_alloc"] == rep["pages_freed"]
    assert rep["high_water_pages"] <= rep["num_pages"]


# ---------------------------------------------------------------------------
# Preemption + resume
# ---------------------------------------------------------------------------
def _preemption_engine():
    # pool of 4x4-token pages shared by 2 slots: two 4-prompt/8-new
    # requests need 3 pages each at peak (6 > 4) -> preemption
    return _engine(B=2, capacity=16, page_size=4, num_pages=4)


def test_preemption_resume_token_bit_identity():
    reqs = [_req(0, plen=4, max_new=8), _req(1, plen=4, max_new=8)]
    baseline = {r.rid: _chain(r.prompt, 8) for r in reqs}

    eng = _preemption_engine()
    for r in reqs:
        eng.add(r)
    done = eng.run()
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["resumes"] >= 1
    assert len(done) == 2
    for r in done:
        assert r.out == baseline[r.rid], \
            f"rid {r.rid} diverged after preemption"
    pre = [r for r in done if r.preemptions]
    assert pre and all(r.t_done is not None for r in done)
    eng.kv.assert_empty()


def test_preemption_victim_is_last_admitted():
    reqs = [_req(0, plen=4, max_new=8), _req(1, plen=4, max_new=8)]
    eng = _preemption_engine()
    for r in reqs:
        eng.add(r)
    done = eng.run()
    by_rid = {r.rid: r for r in done}
    assert by_rid[1].preemptions >= 1         # FIFO: last admitted
    assert by_rid[0].preemptions == 0


def test_unpreempted_run_matches_roomy_pool():
    """Same workload with a roomy pool: no preemptions, same chains."""
    reqs = [_req(0, plen=4, max_new=8), _req(1, plen=4, max_new=8)]
    eng = _engine(B=2, capacity=16, page_size=4, num_pages=8)
    for r in reqs:
        eng.add(r)
    done = eng.run()
    assert eng.stats["preemptions"] == 0
    for r in done:
        assert r.out == _chain(r.prompt, 8)


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------
def test_chunked_prefill_token_identity():
    """Chunk-streamed prefill must generate the same chain as a whole
    prefill -- including the first token, produced from the logits of
    the REAL last prompt token."""
    whole = _engine(B=1)
    chunked = _engine(B=1, prefill_chunk=4)
    for eng in (whole, chunked):
        eng.add(_req(0, plen=12, max_new=4))
    dw, dc = whole.run(), chunked.run()
    assert dw[0].out == dc[0].out == _chain(dw[0].prompt, 4)
    assert chunked.stats["stream_prefill_tokens"] == 8   # 12 - chunk 4
    assert whole.stats["stream_prefill_tokens"] == 0


def test_chunked_prefill_interleaves_with_decode():
    """A long streaming prompt must not stall the other lane's decode:
    the short request makes one token of progress every step."""
    eng = _engine(B=2, prefill_chunk=2)
    long_req = _req(0, plen=10, max_new=2)
    short_req = _req(1, plen=2, max_new=8)
    eng.add(long_req)
    eng.add(short_req)
    eng.step()                                # both admitted
    while long_req.status == "prefill":
        before = len(short_req.out)
        eng.step()
        if short_req.status != "done":
            assert len(short_req.out) == before + 1, \
                "decode lane stalled behind a streaming prefill"
    done = eng.run()
    assert {r.rid for r in done} | {0, 1} == {0, 1}
    assert long_req.out == _chain(long_req.prompt, 2)
    assert short_req.out == _chain(short_req.prompt, 8)


def test_chunked_prefill_pages_grow_with_the_stream():
    eng = _engine(B=1, capacity=32, page_size=4, prefill_chunk=4)
    req = _req(0, plen=12, max_new=2)
    eng.add(req)
    # the admitting step also streams one token (admit-then-decode in
    # the same step): 4 prefilled + 1 streamed = 5 tokens -> 2 pages
    eng.step()
    assert eng.kv.lens[0] == 5 and eng.kv.used_pages == 2
    growth = [eng.kv.used_pages]
    while not req.done:
        eng.step()
        if eng.kv.held(0):
            growth.append(eng.kv.used_pages)
    assert growth == sorted(growth)           # pages only ever grow
    # peak residency: 12 prompt tokens + 1 generated-token KV write
    # (the final token is sampled but never written back)
    assert eng.kv.stats["high_water_pages"] == eng.kv.pages_for(13)
    eng.kv.assert_empty()


# ---------------------------------------------------------------------------
# Deadline-aware admission
# ---------------------------------------------------------------------------
def test_deadline_admission_orders_by_slo():
    eng = _engine(B=1, admission="deadline")
    eng.add(_req(0, plen=2, max_new=2))                      # no SLO
    eng.add(_req(1, plen=2, max_new=2, deadline_ms=500.0))
    eng.add(_req(2, plen=2, max_new=2, deadline_ms=10.0))
    done = eng.run()
    assert [r.rid for r in done] == [2, 1, 0]


def test_fifo_admission_ignores_deadlines():
    eng = _engine(B=1, admission="fifo")
    eng.add(_req(0, plen=2, max_new=2))
    eng.add(_req(1, plen=2, max_new=2, deadline_ms=1.0))
    done = eng.run()
    assert [r.rid for r in done] == [0, 1]


def test_deadline_scheduler_victim_is_latest_deadline():
    kv = PagedKV(num_pages=8, page_size=4)
    sched = Scheduler(SchedulerConfig(admission="deadline"), kv, 64)
    a = _req(0, plen=2, deadline_ms=10.0)
    b = _req(1, plen=2, deadline_ms=900.0)
    c = _req(2, plen=2)                       # no SLO = latest
    for seq, r in enumerate((a, b, c)):
        r._admit_seq = seq
    assert sched.pick_victim([a, b, c]) is c
    assert sched.pick_victim([a, b]) is b
    assert sched.pick_victim([a, b], protect=b) is a


# ---------------------------------------------------------------------------
# Latency accounting
# ---------------------------------------------------------------------------
def test_request_timestamps_are_monotone():
    eng = _engine(B=1)
    eng.add(_req(0, plen=4, max_new=3))
    done = eng.run()
    r = done[0]
    assert r.t_enqueue <= r.t_admit <= r.t_first <= r.t_done
    assert r.queue_ms() >= 0 and r.ttft_ms() > 0
    assert r.ms_per_token() is not None and r.ms_per_token() >= 0


# ---------------------------------------------------------------------------
# Real-model chunked prefill (slow tier)
# ---------------------------------------------------------------------------
@pytest.mark.slow     # LM prefill+decode compile: ~20s
def test_chunked_prefill_matches_whole_on_real_model():
    from repro import configs
    from repro.models.model import LM

    cfg = configs.get_config("qwen2-0.5b", smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab, 12), np.int32)

    outs = []
    for chunk in (None, 8):
        eng = ServeEngine(model, params, batch_slots=2, capacity=32,
                          prefill_chunk=chunk)
        eng.add(Request(rid=0, prompt=prompt.copy(), max_new=5))
        done = eng.run()
        outs.append(done[0].out)
    assert outs[0] == outs[1], \
        "chunk-streamed prefill diverged from whole prefill"


# ---------------------------------------------------------------------------
# The cache is updated in place
# ---------------------------------------------------------------------------
def test_engine_updates_the_cache_in_place(monkeypatch):
    """The decode and merge programs donate the cache: after each
    ``step()`` the buffers it was given are deleted, JAX never warns that
    a donated buffer was not usable, and the served tokens are those of
    an engine with the same seed whose programs copy the cache."""
    import warnings

    from repro import configs
    from repro.models.model import LM
    from repro.serve import engine as engine_mod

    cfg = configs.get_config("qwen2-0.5b", smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (5, 9, 3, 12, 7)]

    def serve(donate):
        eng = ServeEngine(model, params, batch_slots=3, capacity=32, seed=7)
        if not donate:
            eng._decode = jax.jit(model.decode_step)
        for rid, p in enumerate(prompts):
            eng.add(Request(rid=rid, prompt=p, max_new=3 + rid % 4))
        done = []
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            while eng.queue or any(s is not None for s in eng.slots):
                given = jax.tree.leaves(eng.caches)
                done += eng.step()
                assert all(a.is_deleted() for a in given) == donate
        assert not [w for w in caught if "donated" in str(w.message)]
        return {r.rid: r.out for r in done}

    served = serve(True)
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "merge_slot",
                  jax.jit(engine_mod.merge_slot.__wrapped__))
        copied = serve(False)
    assert len(served) == len(prompts)
    assert served == copied
