"""The engine's host spans (docs/architecture.md, "Tracing"): a tiny
`LiveEngine` serves a fetched request and a plain one under a CPU
profiler session; every span is there, nested at its layer boundary,
with its arguments, and every restored chunk names the real request,
sharded fetches included."""
import collections

import jax
import numpy as np
import pytest

from repro.cluster.network import BandwidthTrace
from repro.cluster.storage import StorageCluster, StorageNode
from repro.serving.engine import LiveEngine

SPANS = ("kvf.step", "kvf.fetch.start", "kvf.restore.chunk",
         "kvf.codec.frame", "kvf.restore.h2d", "kvf.cache.restore",
         "kvf.cache.write", "kvf.cache.attend", "kvf.prefill.suffix",
         "kvf.prefill.await", "kvf.prefill.full", "kvf.decode.step")

#: span -> the spans one of which must hold it
PARENTS = {
    "kvf.fetch.start": ("kvf.step",),
    "kvf.restore.chunk": ("kvf.step",),
    "kvf.codec.frame": ("kvf.restore.chunk",),
    "kvf.restore.h2d": ("kvf.restore.chunk",),
    "kvf.cache.restore": ("kvf.restore.chunk",),
    "kvf.prefill.suffix": ("kvf.step",),
    "kvf.prefill.await": ("kvf.prefill.suffix",),
    "kvf.prefill.full": ("kvf.step",),
    "kvf.cache.write": ("kvf.prefill.suffix", "kvf.prefill.full",
                        "kvf.decode.step"),
    "kvf.cache.attend": ("kvf.decode.step",),
    "kvf.decode.step": ("kvf.step",),
}

Span = collections.namedtuple("Span", "name start end args")


def host_spans(log_dir):
    """The ``kvf.*`` spans of the profile's Python thread, with their
    arguments as the profiler keeps them."""
    import glob
    import os
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                if ev.name.startswith("kvf."):
                    out.append(Span(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda s: s.start)


def holds(outer, inner):
    return outer.start <= inner.start and inner.end <= outer.end


@pytest.fixture(scope="module")
def served(tiny_cfg, tiny_params, donor_kv, tmp_path_factory):
    """Per shard count: (engine, fetched request, plain request,
    spans)."""
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, tiny_cfg.vocab_size, 48)
    suffix = rng.integers(0, tiny_cfg.vocab_size, 8)
    plain = rng.integers(0, tiny_cfg.vocab_size, 12)
    kv_k, kv_v = donor_kv(prefix)
    out = {}
    for shards in (None, 2):
        cluster = StorageCluster([StorageNode("n0")])
        cluster.register_prefix(prefix, kv_k, kv_v, tokens_per_chunk=16,
                                resolutions=("240p",))
        eng = LiveEngine(tiny_params, tiny_cfg, cluster, policy="kvfetcher",
                         fetch_mode="async",
                         bandwidth=BandwidthTrace.constant(0.01),
                         adaptive=False, resolution="240p",
                         resolutions=("240p",), mesh_shards=shards)
        # warm every program first, so that the profile holds few
        # compile events
        eng.submit(np.concatenate([prefix, suffix]),
                   reuse_prefix="by-tokens", reuse_tokens=48,
                   max_new_tokens=3)
        eng.submit(plain, max_new_tokens=3)
        eng.run()
        log_dir = str(tmp_path_factory.mktemp(f"spans{shards}"))
        jax.profiler.start_trace(log_dir)
        try:
            fetched = eng.submit(np.concatenate([prefix, suffix]),
                                 reuse_prefix="by-tokens", reuse_tokens=48,
                                 max_new_tokens=3)
            full = eng.submit(plain, max_new_tokens=3)
            eng.run()
        finally:
            jax.profiler.stop_trace()
        assert fetched.tokens_out == full.tokens_out == 3
        out[shards] = (eng, fetched, full, host_spans(log_dir))
    return out


@pytest.mark.parametrize("shards", [None, 2])
def test_every_span_is_there_and_nested_at_its_boundary(served, shards):
    _, _, _, spans = served[shards]
    by = collections.defaultdict(list)
    for s in spans:
        by[s.name].append(s)
    assert set(by) == set(SPANS)
    for name, parents in PARENTS.items():
        for s in by[name]:
            assert any(holds(p, s) for q in parents for p in by[q]), \
                (name, s)
    # the codec's frame spans close before the frame is restored
    for f in by["kvf.codec.frame"]:
        assert not any(holds(f, r) for r in by["kvf.cache.restore"]
                       + by["kvf.restore.h2d"])
    # one h2d copy per restore dispatch, one dispatch per layer per frame
    assert len(by["kvf.restore.h2d"]) == len(by["kvf.cache.restore"]) > 0


@pytest.mark.parametrize("shards", [None, 2])
def test_span_arguments_name_the_real_request_and_the_work(
        served, shards, tiny_cfg):
    eng, fetched, full, spans = served[shards]
    assert eng.n_shards == (shards or 1)
    chunks = [s for s in spans if s.name == "kvf.restore.chunk"]
    assert chunks and {c.args["rid"] for c in chunks} == {fetched.rid}
    assert {c.args["kind"] for c in chunks} == {"k", "v"}
    man = list(eng.store.catalog.values())[0].manifest
    assert sum(c.args["nbytes"] for c in chunks) == \
        man.total_bytes("240p")
    # every token of every layer group, for K and V
    groups = len(man.layer_groups)
    assert sum(c.args["tokens"] for c in chunks) == 48 * groups * 2
    h2d = [s.args["nbytes"] for s in spans if s.name == "kvf.restore.h2d"]
    rows = 48 * tiny_cfg.num_layers * 2
    hd, K = tiny_cfg.head_dim, tiny_cfg.num_kv_heads
    assert sum(h2d) == rows * K * hd + len(h2d) * K * 4
    one = {s.name: s.args for s in spans
           if s.name in ("kvf.fetch.start", "kvf.prefill.suffix",
                         "kvf.prefill.full")}
    assert one["kvf.fetch.start"] == {"rid": fetched.rid}
    assert one["kvf.prefill.suffix"] == {"rid": fetched.rid, "tokens": 8}
    assert one["kvf.prefill.full"] == {"rid": full.rid, "tokens": 12}
    waits = [s.args for s in spans if s.name == "kvf.prefill.await"]
    assert [w["layer"] for w in waits] == list(range(tiny_cfg.num_layers))
    assert {w["rid"] for w in waits} == {fetched.rid}
    assert {s.args["batch"] for s in spans if s.name == "kvf.decode.step"} \
        <= {1, 2}
