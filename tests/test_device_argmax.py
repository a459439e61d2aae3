"""A greedy token is chosen where its logits are (ISSUE 34): the decode
program returns each slot's argmax, a decode step whose rows are all
greedy fetches that ``[slots]`` int32 vector, and a step with one row
that samples fetches the logits and samples on the host as before.

The host path is forced here the way a user would force it: a request
with ``temperature > 0`` rides along, decoding for as long as the
others do, so every decode step of that engine fetches the logits.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.serving.engine as engine_module
from conftest import model_greedy, serving_model_mesh
from paddle_tpu.models.brumby import BrumbyForCausalLM
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny_config
from paddle_tpu.observability import (MetricRegistry, TraceBuffer,
                                      install_trace_buffer, tracing)
from paddle_tpu.serving import SamplingParams, ServingEngine
from paddle_tpu.serving.sampling import ArgmaxRow, sample_token

SLOTS, VOCAB = 4, 128
RIDER = SamplingParams(temperature=0.9, top_k=12, seed=5)


def _llama(seed=0):
    paddle.seed(seed)
    model = LlamaForCausalLM(llama_tiny_config(
        num_hidden_layers=2, hidden_size=64, intermediate_size=128,
        num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64))
    model.eval()
    return model


def _brumby():
    paddle.seed(0)
    model = BrumbyForCausalLM(llama_tiny_config(
        hidden_size=80, num_attention_heads=10, num_key_value_heads=2,
        max_position_embeddings=64))
    model.eval()
    return model


_MODELS = {}


def _model(family):
    if family not in _MODELS:
        _MODELS[family] = _brumby() if family == "brumby" else _llama()
    return _MODELS[family]


def _engine(family="llama", **kw):
    kw = dict(dict(max_slots=SLOTS, max_len=64, min_bucket=8,
                   registry=MetricRegistry()), **kw)
    if family != "brumby":
        kw.setdefault("page_size", 8)
    return ServingEngine(_model(family), **kw)


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 100, (n,)).astype(np.int64) for n in lens]


def _sampled(eng):
    """Decode tokens so far by where they were chosen: (device, host)."""
    m = eng.registry.get("ptpu_serving_sampled_tokens_total")
    return (int(m.labels(where="device").value),
            int(m.labels(where="host").value))


def _serve(eng, prompts, max_new, rider=False):
    """The prompts' greedy tokens; with ``rider`` a sampled request is
    admitted first and outlasts them all."""
    if rider:
        eng.submit(_prompts(99, [4])[0], max_new_tokens=40, sampling=RIDER)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, max_new)]
    eng.run()
    return [list(r.output_ids) for r in reqs]


@pytest.mark.parametrize("family", ["llama", "brumby", "llama-tp2"])
def test_greedy_tokens_equal_the_host_paths(family):
    """The same requests on the device path and on the host path (one
    sampled row riding along), token for token: pages, a recurrent
    state, and a 2-way mesh (the head's vocabulary sharded)."""
    kw = {"mesh": serving_model_mesh(tp=2)} if family == "llama-tp2" else {}
    name = family.split("-")[0]
    prompts, max_new = _prompts(3, [5, 12, 3, 9, 7]), [9, 4, 14, 6, 11]
    dev_eng, host_eng = _engine(name, **kw), _engine(name, **kw)
    got = _serve(dev_eng, prompts, max_new)
    ref = _serve(host_eng, prompts, max_new, rider=True)
    assert got == ref
    # every decode token of the greedy engine came from the program's
    # argmax (a request's first token is its prefill's), and none of
    # the other engine's: the rider was there at every step
    assert _sampled(dev_eng) == (sum(max_new) - len(max_new), 0)
    assert _sampled(host_eng) == (0, sum(max_new) - len(max_new) + 39)
    assert dev_eng.trace_counts["decode"] == 1
    assert host_eng.trace_counts["decode"] == 1
    if name == "llama":
        assert got == [model_greedy(_model(name), p, n)
                       for p, n in zip(prompts, max_new)]


class _Rows:
    """Every reduced row the engine hands ``sample_token``, held against
    its own values: they stayed on the device and cross here."""

    def __init__(self):
        self.real, self.seen = engine_module.sample_token, []

    def __call__(self, row, params, rng):
        tok = self.real(row, params, rng)
        if isinstance(row, ArgmaxRow):
            values = np.asarray(row)
            assert values.shape == (len(row),)
            self.seen.append((values, tok))
        return tok


@pytest.mark.parametrize("fault", ["tie", "nan"])
def test_a_tie_and_a_nan_give_numpys_index(fault, monkeypatch):
    """``np.argmax`` takes the lowest index of a tie and counts a NaN as
    the maximum; so does the program. The head is doctored: two equal
    columns and zeros (every row has a tie, at its top or among the
    zeros), or a NaN column."""
    model = _llama(seed=1)
    w = np.array(model.lm_head.weight._data)
    if fault == "tie":
        col = w[:, 7].copy()
        w[:] = 0.0
        w[:, 40] = w[:, 90] = col
    else:
        w[:, 77] = np.nan
    model.lm_head.weight._data = paddle.to_tensor(w)._data
    rows = _Rows()
    monkeypatch.setattr(engine_module, "sample_token", rows)
    eng = ServingEngine(model, max_slots=SLOTS, max_len=64, min_bucket=8,
                        page_size=8, registry=MetricRegistry())
    reqs = [eng.submit(p, 8) for p in _prompts(4, [6, 3, 11])]
    eng.run()
    assert len(rows.seen) == 3 * 7
    for values, tok in rows.seen:
        assert tok == int(np.argmax(values))
    toks = {t for r in reqs for t in r.output_ids[1:]}
    if fault == "nan":
        assert toks == {77}
    else:
        # the top of a row is the pair (40 first) or, where the pair
        # is negative, the zeros (0 first): never 90
        assert toks <= {0, 40} and toks


def test_the_fetch_says_which_kind_of_step_it_was():
    """``serving.decode.fetch`` carries 4 bytes a slot on a greedy step
    and the logits' size on a step with a sampled row; ``serving.sample``
    and the counter say the same."""
    eng = _engine()
    buf = TraceBuffer(tracing.DEFAULT_CAPACITY)
    prev = install_trace_buffer(buf)
    try:
        for p in _prompts(5, [5, 9, 4]):
            eng.submit(p, 6)
        eng.run()
        greedy = tracing.query()["spans"]
        buf.drain()
        before = _sampled(eng)
        eng.submit(_prompts(6, [7])[0], 4)
        eng.submit(_prompts(6, [5])[0], 6, sampling=RIDER)
        eng.run()
        mixed = tracing.query()["spans"]
    finally:
        install_trace_buffer(prev)

    def decode_steps(spans):
        fetch = [s["attrs"]["bytes"] for s in spans
                 if s["name"] == "serving.decode.fetch"]
        sample = [s["attrs"] for s in spans if s["name"] == "serving.sample"
                  and "device_rows" in s["attrs"]]
        assert len(fetch) == len(sample) > 0
        return fetch, sample

    fetch, sample = decode_steps(greedy)
    assert set(fetch) == {4 * SLOTS}
    assert all(a["device_rows"] == a["rows"] and a["host_rows"] == 0
               for a in sample)
    assert before == (sum(a["rows"] for a in sample), 0) == (15, 0)
    fetch, sample = decode_steps(mixed)
    # the sampled request outlasts the greedy one by two steps: five
    # steps fetch the logits, and a greedy row among them is a host row
    assert fetch == [4 * SLOTS * VOCAB] * 5
    assert [a["host_rows"] for a in sample] == [2, 2, 2, 1, 1]
    assert all(a["device_rows"] == 0 for a in sample)
    assert _sampled(eng) == (15, 8)


def test_a_seeded_sampled_request_replays_token_for_token(monkeypatch):
    """A ``temperature > 0`` request draws from its own seeded stream on
    the host, alone or among greedy rows: the same tokens either way,
    and the ones a fresh stream of its seed draws from the rows it saw."""
    seen = []
    real = engine_module.sample_token

    def spy(row, params, rng):
        if params.temperature > 0:
            seen.append(np.array(row))
        return real(row, params, rng)

    monkeypatch.setattr(engine_module, "sample_token", spy)
    prompt = _prompts(8, [6])[0]
    alone = _engine()
    a = alone.submit(prompt, 10, sampling=RIDER)
    alone.run()
    rows, seen[:] = list(seen), []
    among = _engine()
    others = [among.submit(p, 7) for p in _prompts(9, [4, 8])]
    b = among.submit(prompt, 10, sampling=RIDER)
    among.run()
    assert a.output_ids == b.output_ids and len(a.output_ids) == 10
    rng = np.random.RandomState(RIDER.seed)
    assert a.output_ids == [sample_token(r, RIDER, rng) for r in rows]
    assert all(np.array_equal(x, y) for x, y in zip(rows, seen))
    assert [o.output_ids for o in others] == [
        model_greedy(_model("llama"), o.prompt, 7) for o in others]
    assert _sampled(alone) == (0, 9)


def test_a_reduced_row_serves_greedy_requests_only():
    row = ArgmaxRow(np.zeros((2, 16), np.float32), 1, 3)
    assert len(row) == 16
    assert sample_token(row, SamplingParams(), None) == 3
    with pytest.raises(ValueError, match="temperature"):
        sample_token(row, RIDER, np.random.RandomState(0))


def test_the_benchmarks_planted_fault_reaches_the_outputs():
    """``chipbench.serving_loop.altered_tokens`` patches
    ``engine.sample_token`` and alters every n-th token it returns: the
    seam is alive on the device path. One request, so call ``k`` gives
    output ``k - 1``: every fifth output is the neighbour of the token
    the model puts first after the (altered) sequence so far, the others
    are that token."""
    from chipbench.serving_loop import altered_tokens
    model, prompt = _model("llama"), _prompts(10, [9])[0]
    eng = _engine()
    with altered_tokens(every=5):
        req = eng.submit(prompt, 17)
        eng.run()
    assert _sampled(eng) == (16, 0)
    ids = np.concatenate([prompt, req.output_ids])
    logits = model(paddle.to_tensor(ids[None])).numpy()[0]
    first = logits[len(prompt) - 1:-1].argmax(-1)
    want = [(t + 1) % VOCAB if (i + 1) % 5 == 0 else t
            for i, t in enumerate(first.tolist())]
    assert req.output_ids == want
    assert req.output_ids != model_greedy(model, prompt, 17)
