"""The traffic generator: one seed gives the same requests, another
seed other tokens over the same sizes in the same order."""
import numpy as np
import pytest

from chipbench import bench, traffic

MIXES = ["doc-qa", "doc-qa-fresh"]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests_other_seed_other_tokens(name):
    mix = bench.traffic(name)
    a = traffic.generate(mix, 2**33 + 1, 64000)
    b = traffic.generate(mix, 2**33 + 1, 64000)
    c = traffic.generate(mix, 2**33 + 2, 64000)
    for x, y in zip(a.documents, b.documents):
        np.testing.assert_array_equal(x, y)
    assert [[(q.doc, q.question.tolist()) for q in cl] for cl in a.asks] \
        == [[(q.doc, q.question.tolist()) for q in cl] for cl in b.asks]
    assert any(not np.array_equal(x, y)
               for x, y in zip(a.documents, c.documents))
    assert [len(d) for d in a.documents] == traffic.lengths(mix) \
        == [len(d) for d in c.documents]
    assert [[q.doc for q in cl] for cl in a.asks] == \
        [[q.doc for q in cl] for cl in c.asks]
    assert any(not np.array_equal(x.question, y.question)
               for x, y in zip(a.asks[0], c.asks[0]))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_same_set_of_sizes(name):
    mix = bench.traffic(name)
    n_docs = len(traffic.lengths(mix))
    for seed in (0, 7, 2**40):
        t = traffic.generate(mix, seed, 64000)
        assert len(t.asks) == mix["clients"]
        for cl in t.asks:
            assert len(cl) == mix["requests_per_client"]
            # a fixed rotation from the client's own first document
            assert [q.doc for q in cl] == [(cl[0].client + k) % n_docs
                                           for k in range(len(cl))]
            assert all(len(q.question) == mix["question_tokens"]
                       and q.answer_tokens == mix["answer_tokens"]
                       for q in cl)
        assert t.longest_request == max(traffic.lengths(mix)) + \
            mix["question_tokens"] + mix["answer_tokens"]


def test_warmup_drains_one_sequence_at_a_time_per_document():
    """The warm-up's served requests: each document once, fetched and
    prefilled with a one-token answer, so no decode step runs in them
    (`run.warm_decode` steps decode at every shape instead)."""
    mix = bench.traffic("doc-qa")
    t = traffic.generate(mix, 5, 64000)
    asks = traffic.warmup_asks(t, 5)
    assert [q.doc for q in asks] == list(range(len(mix["documents"])))
    assert all(q.answer_tokens == 1 for q in asks)
    assert all(len(q.question) == mix["question_tokens"] for q in asks)
    window = {q.question.tobytes() for cl in t.asks for q in cl}
    assert not any(q.question.tobytes() in window for q in asks)


def test_open_loop_and_missing_keys_are_refused():
    mix = dict(bench.traffic("doc-qa"), loop="open")
    with pytest.raises(ValueError, match="closed-loop"):
        traffic.generate(mix, 1, 100)
    mix = dict(bench.traffic("doc-qa"))
    del mix["clients"]
    with pytest.raises(ValueError, match="clients"):
        traffic.generate(mix, 1, 100)


def test_the_fresh_mix_sends_the_doc_qa_prompts_and_reuses_nothing():
    """Reuse against recompute on the same requests: one seed gives the
    two mixes the same prompts, and only doc-qa stores its contexts."""
    qa, fresh = bench.traffic("doc-qa"), bench.traffic("doc-qa-fresh")
    a = traffic.generate(qa, 2**33 + 9, 64000)
    b = traffic.generate(fresh, 2**33 + 9, 64000)
    assert a.reuses and not b.reuses
    for ca, cb in zip(a.asks, b.asks):
        for x, y in zip(ca, cb):
            np.testing.assert_array_equal(a.prompt(x), b.prompt(y))
    assert a.request_lengths == b.request_lengths


def test_a_mix_gives_its_lengths_as_documents_or_contexts():
    """The key that gives the lengths says whether they are stored: a mix
    with neither, or with both, is refused."""
    fresh = bench.traffic("doc-qa-fresh")
    assert "documents" not in fresh and not traffic.generate(
        fresh, 1, 100).reuses
    with pytest.raises(ValueError, match="exactly one"):
        traffic.check_mix(dict(fresh, documents=fresh["contexts"]))
    neither = dict(fresh)
    del neither["contexts"]
    with pytest.raises(ValueError, match="exactly one"):
        traffic.check_mix(neither)
    assert traffic.generate(dict(neither, documents=[3, 4]), 1, 100).reuses
