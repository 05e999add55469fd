"""Continuous-batching engine correctness (no cluster: engine-in-process).

The load-bearing claim: in-flight batching is *schedule-invariant* — a
sequence's greedy tokens are identical whether it decodes alone or joins
a running batch mid-flight with mixed lengths (the fixed decode shape +
per-sequence positions/PRNG make batch composition invisible). Plus:
KV blocks free the moment a sequence finishes, and KV exhaustion sheds
with the serve plane's typed overload error instead of hanging.
"""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import generation as G  # noqa: E402
from ray_tpu.models.transformer import TransformerConfig, init_params  # noqa: E402
from ray_tpu.serve.exceptions import DeploymentOverloadedError  # noqa: E402
from ray_tpu.serve.llm.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.serve.llm.kv_cache import BlockTable  # noqa: E402

CFG = TransformerConfig(
    vocab_size=97,
    d_model=32,
    n_layers=2,
    n_heads=4,
    n_kv_heads=2,  # GQA path exercised
    d_ff=64,
    max_seq_len=128,
    dtype=jnp.float32,
)
ECFG = EngineConfig(
    block_size=4,
    num_blocks=64,
    max_batch=3,
    max_blocks_per_seq=16,
    max_waiting=16,
    stream_timeout_s=60.0,
)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture
def engine(params):
    eng = InferenceEngine(params, CFG, ECFG, deployment="test-llm")
    yield eng
    eng.shutdown()


def _prompts(n, lo=3, hi=13, seed=2):
    rs = np.random.RandomState(seed)
    return [list(rs.randint(1, CFG.vocab_size, size=rs.randint(lo, hi))) for _ in range(n)]


def test_continuous_matches_isolated_greedy(params, engine):
    """Staggered arrivals + mixed lengths through the shared engine emit
    tokenwise-identical greedy outputs to each prompt decoded in
    isolation (dense static path AND solo engine run)."""
    prompts = _prompts(7)
    dense = [
        np.asarray(G.generate(params, p, CFG, max_new_tokens=9))[0].tolist()
        for p in prompts
    ]
    streams = []
    for i, p in enumerate(prompts):
        streams.append(engine.submit(p, max_new_tokens=9))
        time.sleep(0.01 * (i % 3))  # stagger so cohorts genuinely mix
    outs = [s.tokens() for s in streams]
    assert outs == dense
    # and a solo engine pass (paged, batch of one) agrees too
    solo = InferenceEngine(params, CFG, ECFG, deployment="test-llm-solo")
    try:
        assert solo.submit(prompts[0], max_new_tokens=9).tokens() == dense[0]
    finally:
        solo.shutdown()


def test_sampling_seeded_and_batch_invariant(params, engine):
    """temperature/top-k sampling is keyed by (seed, step) per sequence:
    the same request samples the same tokens alone or mid-batch."""
    prompt = _prompts(1, seed=5)[0]
    kw = dict(max_new_tokens=8, temperature=0.9, top_k=5, seed=123)
    alone = engine.submit(prompt, **kw).tokens()
    # resubmit surrounded by greedy neighbours occupying the other slots
    neighbours = [
        engine.submit(p, max_new_tokens=12) for p in _prompts(2, seed=6)
    ]
    again = engine.submit(prompt, **kw).tokens()
    for s in neighbours:
        s.tokens()
    assert again == alone
    # a different seed moves the sample (sanity: not argmax in disguise)
    other = engine.submit(prompt, **dict(kw, seed=124)).tokens()
    assert other != alone or len(alone) <= 2


def test_greedy_default_unchanged_by_sampling_params(params, engine):
    """temperature=0 stays bitwise-stable regardless of top_k/seed."""
    prompt = _prompts(1, seed=9)[0]
    a = engine.submit(prompt, max_new_tokens=6).tokens()
    b = engine.submit(prompt, max_new_tokens=6, top_k=3, seed=77).tokens()
    assert a == b


def test_blocks_free_immediately_on_finish(params, engine):
    """A short sequence finishing mid-batch returns its blocks while a
    long neighbour is still decoding — reclamation is per-sequence, not
    per-cohort."""
    long_s = engine.submit(_prompts(1, seed=11)[0], max_new_tokens=40)
    short_s = engine.submit(_prompts(1, seed=12)[0], max_new_tokens=2)
    short_s.tokens()  # drained: finished
    deadline = time.time() + 10
    saw_reclaim = False
    while time.time() < deadline:
        st = engine.kv_stats()
        if st["running"] == 1 and st["blocks_committed"] > 0:
            saw_reclaim = True
            break
        time.sleep(0.02)
    long_s.tokens()
    assert saw_reclaim, "short sequence's finish did not free its slot early"
    st = engine.kv_stats()
    assert st["blocks_free"] == st["blocks_total"]
    assert st["blocks_committed"] == 0


def test_kv_exhaustion_sheds_typed_never_hangs(params):
    """Admission over a tiny pool: excess submits fail FAST with the typed
    overload error (retry_after set), admitted work still completes, and
    nothing hangs."""
    eng = InferenceEngine(
        params,
        CFG,
        EngineConfig(
            block_size=4,
            num_blocks=9,  # 8 usable blocks
            max_batch=2,
            max_blocks_per_seq=8,
            max_waiting=1,
            stream_timeout_s=30.0,
        ),
        deployment="test-llm-tiny",
    )
    try:
        prompt = _prompts(1, seed=3)[0][:6]
        admitted, shed = [], []
        t0 = time.perf_counter()
        for _ in range(10):
            try:
                admitted.append(eng.submit(prompt, max_new_tokens=8))
            except DeploymentOverloadedError as e:
                shed.append(e)
        elapsed = time.perf_counter() - t0
        assert shed, "tiny pool never shed"
        assert admitted, "everything shed"
        assert elapsed < 5.0, f"shedding took {elapsed:.1f}s — queued, not shed"
        for e in shed:
            assert e.retry_after_s > 0
            assert e.capacity == 8
        for s in admitted:
            assert len(s.tokens()) == 8  # admitted work unaffected
        st = eng.kv_stats()
        assert st["blocks_free"] == st["blocks_total"]
    finally:
        eng.shutdown()


def test_submit_rejects_oversized_context(params, engine):
    with pytest.raises(ValueError):
        engine.submit([1] * 100, max_new_tokens=1000)


def test_eos_token_stops_early(params, engine):
    """Whatever greedy emits first, declaring it the eos stops the
    stream at one token with reason 'stop'."""
    prompt = _prompts(1, seed=4)[0]
    first = engine.submit(prompt, max_new_tokens=5).tokens()[0]
    s = engine.submit(prompt, max_new_tokens=5, eos_token=first)
    assert s.tokens() == [first]
    assert s.finish_reason == "stop"


def test_shutdown_fails_streams_typed(params):
    eng = InferenceEngine(params, CFG, ECFG, deployment="test-llm-down")
    streams = [eng.submit(p, max_new_tokens=50) for p in _prompts(3, seed=8)]
    eng.shutdown()
    outcomes = []
    for s in streams:
        try:
            s.tokens()
            outcomes.append("done")
        except RuntimeError:
            outcomes.append("typed")
        except TimeoutError:
            outcomes.append("hang")
    assert "hang" not in outcomes


def test_generate_top_k_sampling(params):
    """Satellite: generate() grows top-k; greedy default is untouched."""
    prompt = _prompts(1, seed=10)[0]
    g1 = np.asarray(G.generate(params, prompt, CFG, max_new_tokens=6))
    g2 = np.asarray(G.generate(params, prompt, CFG, max_new_tokens=6, top_k=4))
    assert (g1 == g2).all(), "top_k must not perturb greedy decode"
    key = jax.random.PRNGKey(1)
    s1 = np.asarray(
        G.generate(
            params, prompt, CFG, max_new_tokens=6, temperature=0.8, top_k=3, key=key
        )
    )
    s2 = np.asarray(
        G.generate(
            params, prompt, CFG, max_new_tokens=6, temperature=0.8, top_k=3, key=key
        )
    )
    assert (s1 == s2).all(), "same key must reproduce the same sample"


def test_sample_token_top_k_masks_tail():
    """top_k=1 sampling degenerates to argmax for any key."""
    logits = jnp.asarray(np.random.RandomState(0).randn(4, 33), jnp.float32)
    for i in range(3):
        tok = G.sample_token(
            logits, temperature=1.0, top_k=1, key=jax.random.PRNGKey(i)
        )
        assert (np.asarray(tok) == np.asarray(logits).argmax(-1)).all()


# -- one decode step ahead: tokens stay on the device, the host reads late -----

WIDE = EngineConfig(
    block_size=4, num_blocks=128, max_batch=5, max_blocks_per_seq=16, max_waiting=16,
    stream_timeout_s=60.0,
)


def _by_hand(params, prompt, n, ecfg=WIDE):
    """Greedy tokens of one prompt from the engine's own programs, driven
    step by step from here: prefill, host argmax, then ``_decode_greedy`` with
    the sequence alone in slot 0."""
    eng = InferenceEngine(params, CFG, ecfg, deployment="by-hand", start=False)
    try:
        b, mb = ecfg.max_batch, ecfg.max_blocks_per_seq
        table = BlockTable(eng._alloc)
        table.reserve(len(prompt))
        table.length = len(prompt)
        toks = np.zeros((1, eng._bucket(len(prompt))), np.int32)
        toks[0, : len(prompt)] = prompt
        logits, eng._pool = eng._prefill(
            params, jnp.asarray(toks), jnp.asarray([table.as_list(mb)], jnp.int32), eng._pool,
            jnp.int32(len(prompt)))
        out = [int(np.asarray(logits[0]).argmax())]
        while len(out) < n:
            tokens, positions = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
            tables, active = np.zeros((b, mb), np.int32), np.zeros((b,), bool)
            tokens[0], positions[0], active[0] = out[-1], table.length, True
            table.append_token()
            tables[0] = table.as_list(mb)
            nxt, eng._pool = eng._decode_greedy(
                params, jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(tables), eng._pool,
                jnp.asarray(active))
            out.append(int(np.asarray(nxt)[0]))
        return out
    finally:
        eng.shutdown()


def _idle(eng, timeout=10.0):
    deadline = time.time() + timeout
    while eng._has_active() and time.time() < deadline:
        time.sleep(0.01)
    assert not eng._has_active()
    time.sleep(0.05)  # the loop folds its last iteration after the last token is out


def _assert_all_free(eng):
    st = eng.kv_stats()
    assert st["blocks_free"] == st["blocks_total"] and st["blocks_committed"] == 0
    assert st["running"] == 0 and not eng._flight


def _records(eng):
    stats = eng.loop_stats(records=10_000)
    return stats, [dict(zip(stats["fields"], r)) for r in stats["records"]]


@pytest.mark.parametrize("n", [1, 3, WIDE.max_batch])
def test_running_ahead_gives_the_tokens_of_a_loop_by_hand(params, n):
    """Concurrent greedy requests of uneven lengths, the loop two steps deep:
    each stream is token for token the by-hand loop over ``_prefill`` and
    ``_decode_greedy``."""
    prompts = _prompts(n, seed=20 + n)
    lengths = [5 + 4 * i for i in range(n)]
    want = [_by_hand(params, p, m) for p, m in zip(prompts, lengths)]
    eng = InferenceEngine(params, CFG, WIDE, deployment=f"ahead-{n}")
    try:
        streams = [eng.submit(p, max_new_tokens=m) for p, m in zip(prompts, lengths)]
        assert [s.tokens() for s in streams] == want
        assert [s.finish_reason for s in streams] == ["length"] * n
        _idle(eng)
        _assert_all_free(eng)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("m", [1, 2, 3])
def test_short_answers_end_by_length(params, engine, m):
    """One, two and three tokens: the prefill's alone, then one and two
    steps, where the loop has not yet two in flight."""
    prompt = _prompts(1, seed=31)[0]
    s = engine.submit(prompt, max_new_tokens=m)
    assert s.tokens() == _by_hand(params, prompt, m, ECFG) and s.finish_reason == "length"
    _idle(engine)
    _assert_all_free(engine)


def test_eos_is_seen_a_step_late_and_nothing_follows_it(params, ray_start_regular):
    """The sequence rides one more step, whose row is dropped: the stream
    stops at the EOS, its blocks go back once, the records count one row."""
    prompt = _prompts(1, seed=4)[0]
    ref = _by_hand(params, prompt, 12, ECFG)
    # a token that first appears mid-stream, with steps left to overrun into
    at = next(i for i in range(2, 9) if ref[i] not in ref[:i])
    eng = InferenceEngine(params, CFG, ECFG, deployment="ahead-eos")
    try:
        s = eng.submit(prompt, max_new_tokens=12, eos_token=ref[at])
        assert s.tokens() == ref[: at + 1] and s.finish_reason == "stop"
        _idle(eng)
        assert s._q.empty()  # nothing after the 'done'
        _assert_all_free(eng)
        stats, recs = _records(eng)
        assert stats["overrun"]["sum"] == 1 == sum(r["overrun"] for r in recs)
        assert eng.decode_steps == at + 1  # the EOS's step, and the one that was in flight behind it
        assert stats["overrun"]["count"] == eng.decode_steps
        (req,) = [dict(zip(stats["request_fields"], r)) for r in stats["requests"]]
        assert req["reason"] == "stop" and req["tokens"] == at + 1
    finally:
        eng.shutdown()


def test_a_newcomer_among_two_steps_in_flight_gets_its_first_token_in_order(params, ray_start_regular):
    """Admitted while the loop is two steps deep, a request's prefill is
    enqueued and not waited for; its first token is read before the result of
    the step dispatched after its prefill, and before its own second token."""
    pa, pb = _prompts(2, seed=40)
    eng = InferenceEngine(params, CFG, ECFG, deployment="ahead-new")
    try:
        a = eng.submit(pa, max_new_tokens=48)
        it = iter(a)
        head = [next(it) for _ in range(6)]  # the loop is in its steady state
        b = eng.submit(pb, max_new_tokens=5)
        got_b = b.tokens()
        got_a = head + list(it)
        _idle(eng)
        stats, recs = _records(eng)
    finally:
        eng.shutdown()
    assert got_a == _by_hand(params, pa, 48, ECFG) and got_b == _by_hand(params, pb, 5, ECFG)
    reqs = {r["request"]: r for r in (dict(zip(stats["request_fields"], r)) for r in stats["requests"])}
    rb = reqs[b.request_id]
    (i_admit,) = [i for i, r in enumerate(recs) if r["t_loop"] == rb["t_admit"]]
    admit = recs[i_admit]
    # two steps were in flight at the top of that iteration: it read one and dispatched behind the other
    assert admit["prefills"] == 1 and admit["t_result"] and admit["ahead"] == 1
    # the k-th record that read a result read step k; the step dispatched in
    # the admitting iteration is the first one behind the prefill
    readers = [r for r in recs if r["t_result"]]
    assert [r["overrun"] for r in readers] == [0] * len(readers)
    after = readers[admit["step"] - 1]
    assert after["t_admit_end"] <= rb["t_first"] <= after["t_result"]
    # and no earlier result was held back for it: the iteration between read its step as ever
    between = readers[admit["step"] - 2]
    assert between["t_result"] < rb["t_first"] and between["ahead"] == 1
    assert b.ttft_s == pytest.approx((rb["t_first"] - rb["t_submit"]) / 1e9, abs=1e-6)


def test_a_sampling_request_among_greedy_ones_puts_the_loop_back_in_step(params, ray_start_regular):
    """From the iteration it is admitted to the one it ends in, nothing is
    dispatched ahead; its tokens are those it samples alone, the neighbours'
    those of the by-hand loop; then the loop runs ahead again."""
    pa, pb = _prompts(2, seed=50)
    kw = dict(max_new_tokens=6, temperature=0.9, top_k=5, seed=321)
    eng = InferenceEngine(params, CFG, ECFG, deployment="ahead-sampled")
    try:
        alone = eng.submit(pb, **kw).tokens()
        _idle(eng)
        first_step = eng.decode_steps
        a = eng.submit(pa, max_new_tokens=48)
        it = iter(a)
        head = [next(it) for _ in range(6)]
        among = eng.submit(pb, **kw)
        assert among.tokens() == alone
        assert head + list(it) == _by_hand(params, pa, 48, ECFG)
        _idle(eng)
        _assert_all_free(eng)
        stats, recs = _records(eng)
    finally:
        eng.shutdown()
    reqs = [dict(zip(stats["request_fields"], r)) for r in stats["requests"]]
    rb = next(r for r in reqs if r["request"] == among.request_id)
    mine = [r for r in recs if r["step"] > first_step and r["live"]]
    during = [r for r in mine if rb["t_admit"] <= r["t_loop"] <= rb["t_finish"]]
    assert len(during) >= 5 and all(r["ahead"] == 0 for r in during)
    assert any(r["fused"] == 0 for r in during)
    before = [r for r in mine if r["t_loop"] < rb["t_admit"]]
    later = [r for r in mine if r["t_loop"] > rb["t_finish"]]
    assert sum(r["ahead"] for r in before) >= len(before) - 1  # all but the first step from idle
    assert len(later) >= 20 and sum(r["ahead"] for r in later) >= len(later) - 1


class _Unreadable:
    """A step's result whose copy to the host fails."""

    def __array__(self, *a, **k):
        raise RuntimeError("device fault")


@pytest.mark.parametrize("where", ["dispatch", "result"])
def test_a_failing_step_with_two_in_flight_fails_each_stream_once(params, where):
    eng = InferenceEngine(params, CFG, ECFG, deployment=f"ahead-fail-{where}")
    real, calls = eng._decode_greedy, []

    def flaky(*args):
        calls.append(1)
        if len(calls) == 5:
            if where == "dispatch":
                raise RuntimeError("device fault")
            return _Unreadable(), real(*args)[1]
        return real(*args)

    eng._decode_greedy = flaky
    try:
        streams = [eng.submit(p, max_new_tokens=30) for p in _prompts(3, seed=60)]
        for s in streams:
            with pytest.raises((RuntimeError, TypeError)):
                s.tokens()
        _idle(eng)
        for s in streams:
            assert s._q.empty()  # one failure a stream, and nothing behind it
        _assert_all_free(eng)
        # the engine goes on serving
        prompt = _prompts(1, seed=61)[0]
        assert eng.submit(prompt, max_new_tokens=4).tokens() == _by_hand(params, prompt, 4, ECFG)
    finally:
        eng.shutdown()
    _assert_all_free(eng)


def test_shutdown_with_steps_in_flight_leaves_nothing_unread(params):
    eng = InferenceEngine(params, CFG, ECFG, deployment="ahead-down")
    streams = [eng.submit(p, max_new_tokens=48) for p in _prompts(3, seed=70)]
    its = [iter(s) for s in streams]
    heads = [[next(it) for _ in range(4)] for it in its]  # two steps are in flight from here on
    eng.shutdown()
    for s, it, head in zip(streams, its, heads):
        with pytest.raises(RuntimeError, match="shut down"):
            list(it)
        assert s._q.empty()
    assert len(heads) == 3
    _assert_all_free(eng)
    assert eng._steps_retired == eng.decode_steps  # every step that went out was read
