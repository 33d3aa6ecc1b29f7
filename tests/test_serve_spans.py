"""Host spans and counters of the serve loop, the stable names of its
programs, and the name scopes of the model's attention and
dequantization.

Stub model: next token ``(7*t + 3 + 2*pos) % vocab``, as in
``test_serve_engine.py``; the cache-merge identity and the name-scope
checks use the smoke configurations of real models.
"""

import glob
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro import configs
from repro.models import qweight
from repro.models.model import LM
from repro.serve import engine as engine_mod
from repro.serve import spans
from repro.serve.engine import Request, ServeEngine, merge_slot

VOCAB = 32

#: each span's direct children (docs/serve.md, "Observability")
TREE = {"step": ("prefill", "decode"),
        "prefill": ("prefill_launch", "merge", "first_token"),
        "decode": ("kv_append", "probe", "decode_launch", "sync", "lanes")}


class _Stub:
    def __init__(self, vocab=VOCAB, d=8):
        self.vocab = vocab
        self.embed = np.random.default_rng(0).normal(
            size=(vocab, d)).astype(np.float32)

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


class _Probe:
    done = False
    faults = None
    escaped_outputs = 0

    def observe(self, x):
        pass


def _engine(**kw):
    return ServeEngine(_Stub(), params={}, batch_slots=kw.pop("B", 2),
                       capacity=kw.pop("capacity", 32), **kw)


def _run(eng, n=6, plen=3, max_new=3):
    for rid in range(n):
        prompt = ((np.arange(plen + rid % 3) * 5 + rid) % VOCAB).astype(
            np.int32)
        eng.add(Request(rid=rid, prompt=prompt, max_new=max_new))
    return eng.run()


# ---------------------------------------------------------------------------
# The span helper
# ---------------------------------------------------------------------------
def test_span_adds_seconds_and_count_and_nests():
    st = spans.counters(("outer", "inner"))
    with spans.Span(st, "outer"):
        for _ in range(3):
            with spans.Span(st, "inner", rid=4) as sp:
                sp.annotate(bucket=8)
                time.sleep(0.002)
    assert st["outer_n"] == 1 and st["inner_n"] == 3
    assert st["inner_s"] >= 3 * 0.002
    assert st["outer_s"] >= st["inner_s"]


def test_span_counts_a_raising_body_and_lets_it_raise():
    st = spans.counters(("x",))
    with pytest.raises(KeyError):
        with spans.Span(st, "x"):
            raise KeyError("boom")
    assert st["x_n"] == 1


# ---------------------------------------------------------------------------
# Counters on the engine
# ---------------------------------------------------------------------------
def test_every_span_key_is_present_and_zero_on_a_fresh_engine():
    eng = _engine()
    for n in spans.NAMES:
        assert eng.stats[f"{n}_s"] == 0.0, n
        assert eng.stats[f"{n}_n"] == 0, n
    assert set(TREE) | {c for cs in TREE.values() for c in cs} == \
        set(spans.NAMES)
    # the cold/warm decode split is gone
    assert not any(k.startswith("decode_cold") or k.startswith("decode_warm")
                   for k in eng.stats)


def test_counts_tie_to_admissions_and_decode_launches():
    eng = _engine(B=2)
    done = _run(eng, n=6)
    st = eng.stats
    assert len(done) == 6
    assert st["prefill_n"] == st["admitted"] == 6
    assert st["prefill_launch_n"] == st["merge_n"] == st["admitted"]
    # every admission here is a whole prefill: one first-token sync each
    assert st["first_token_n"] == st["admitted"]
    assert st["decode_n"] > 0
    assert st["sync_n"] == st["decode_n"] == st["decode_launch_n"] \
        == st["lanes_n"] == st["kv_append_n"]
    assert st["probe_n"] == 0                  # no probe set
    assert st["step_n"] >= st["steps"]


def test_a_probe_gets_one_span_per_decode():
    eng = _engine(B=2, fabric_probe=_Probe())
    _run(eng, n=3)
    assert eng.stats["probe_n"] == eng.stats["decode_n"] > 0


def test_chunked_prefill_skips_the_first_token_sync():
    eng = _engine(B=1, prefill_chunk=4)
    eng.add(Request(rid=0, prompt=np.arange(10, dtype=np.int32) % VOCAB,
                    max_new=2))
    eng.run()
    st = eng.stats
    assert st["prefill_n"] == 1 and st["first_token_n"] == 0
    assert st["stream_prefill_tokens"] == 6


def test_each_span_holds_at_least_the_sum_of_its_children():
    eng = _engine(B=2, fabric_probe=_Probe())
    _run(eng, n=6)
    st = eng.stats
    for parent, kids in TREE.items():
        assert st[f"{parent}_s"] >= sum(st[f"{k}_s"] for k in kids) - 1e-9, \
            parent
        assert all(st[f"{k}_s"] > 0 for k in kids if k != "first_token"
                   or st["first_token_n"])


def test_prefill_s_still_covers_the_whole_admission(monkeypatch):
    """``prefill_s`` is the whole ``_prefill_into``: the prefill program,
    the merge and the first-token sync, and no more than the call."""
    delay = 0.01
    eng = _engine(B=2)
    launch = eng._prefill_one

    def slow_launch(*a):
        time.sleep(delay)
        return launch(*a)

    def slow_merge(*a):
        time.sleep(delay)
        return merge_slot(*a)

    eng._prefill_one = slow_launch
    monkeypatch.setattr(engine_mod, "merge_slot", slow_merge)
    outer = [0.0]
    inner = eng._prefill_into

    def timed(i, req):
        t = time.perf_counter()
        inner(i, req)
        outer[0] += time.perf_counter() - t

    eng._prefill_into = timed
    _run(eng, n=4)
    st = eng.stats
    assert st["admitted"] == 4
    assert st["prefill_s"] >= 2 * delay * st["admitted"]
    assert st["prefill_s"] >= (st["prefill_launch_s"] + st["merge_s"]
                               + st["first_token_s"]) - 1e-9
    assert st["prefill_launch_s"] >= delay * st["admitted"]
    assert st["merge_s"] >= delay * st["admitted"]
    assert st["prefill_s"] <= outer[0]


# ---------------------------------------------------------------------------
# The spans in a profiler trace
# ---------------------------------------------------------------------------
def test_profiler_trace_nests_the_spans_and_tags_the_request(tmp_path):
    eng = _engine(B=2)
    _run(eng, n=2)                    # compiles outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.add(Request(rid=41, prompt=np.arange(5, dtype=np.int32),
                        max_new=3))
        eng.run()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    events = [e for plane in ProfileData.from_file(files[0]).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    by = {}
    for e in events:
        by.setdefault(e.name, []).append(e)
    steps = by["serve.step"]

    def inside_a_step(e):
        return any(s.start_ns <= e.start_ns and e.end_ns <= s.end_ns
                   for s in steps)

    for name in ("serve.sync", "serve.lanes", "serve.merge",
                 "serve.first_token", "serve.decode_launch"):
        assert by.get(name), name
        assert all(inside_a_step(e) for e in by[name]), name
    (pre,) = by["serve.prefill"]
    ids = {k: v for k, v in pre.stats}
    assert ids["rid"] == 41 and ids["bucket"] == 8
    assert inside_a_step(pre)


# ---------------------------------------------------------------------------
# Stable program names
# ---------------------------------------------------------------------------
def test_prefill_and_merge_lower_under_stable_names():
    eng = _engine(B=2)
    low = eng._prefill_one.lower(eng.params, jnp.zeros((1, 8), jnp.int32))
    assert low.as_text().startswith("module @jit_prefill ")
    _, one = eng._prefill_one(eng.params, jnp.zeros((1, 8), jnp.int32))
    low = merge_slot.lower(eng.caches, one, np.int32(1))
    assert low.as_text().startswith("module @jit_merge_slot ")


def _eager_merge(caches, one, i):
    """The engine's merge before it was one program: one eager
    ``.at[].set`` per cache leaf."""
    i = int(i)

    def merge(path, full, src):
        keys = [getattr(q, "key", str(q)) for q in path
                if hasattr(q, "key")]
        bdim = 1 if "unit" in keys else 0
        idx = (slice(None),) * bdim + (i,)
        return full.at[idx].set(src[(slice(None),) * bdim + (0,)])

    return jax.tree_util.tree_map_with_path(merge, caches, one)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "recurrentgemma-9b"])
def test_merge_slot_caches_are_bit_identical_to_the_eager_merge(
        arch, monkeypatch):
    """qwen2 holds only scanned ("unit") caches; recurrentgemma also
    unstacked ("rest") ones, whose batch dim is 0."""
    cfg = configs.get_config(arch, smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, 8).astype(np.int32)
               for _ in range(5)]

    def serve(merge):
        monkeypatch.setattr(engine_mod, "merge_slot", merge)
        eng = ServeEngine(model, params, batch_slots=3, capacity=32)
        for rid, p in enumerate(prompts):
            eng.add(Request(rid=rid, prompt=p, max_new=2 + rid % 3))
        done = eng.run()
        # the last admissions' merges are what the caches end with
        return eng.caches, {r.rid: r.out for r in done}

    new, out_new = serve(merge_slot)
    old, out_old = serve(_eager_merge)
    assert out_new == out_old
    ln, lo = jax.tree_util.tree_leaves(new), jax.tree_util.tree_leaves(old)
    assert len(ln) == len(lo)
    for a, b in zip(ln, lo):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


# ---------------------------------------------------------------------------
# Name scopes in the decode step
# ---------------------------------------------------------------------------
def test_decode_step_carries_the_attention_and_dq_scopes():
    cfg = configs.get_config("qwen2-0.5b", smoke=True)
    model = LM(cfg)
    params = qweight.quantize_tree(model.init(jax.random.PRNGKey(0)), bits=4)
    caches = model.init_cache(2, 64)
    txt = jax.jit(model.decode_step).lower(
        params, caches, jnp.zeros((2, 1), jnp.int32),
        jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    locs = set(re.findall(r'loc\("([^"]*)"', txt))
    att = [s for s in locs if re.search(r"(^|/)attention/", s)]
    dq = [s for s in locs if re.search(r"(^|/)dq/", s)]
    assert any("dot_general" in s for s in att), sorted(locs)[:20]
    assert dq
    # the projections stay outside the attention scope
    assert not any("dq/" in s for s in att)
