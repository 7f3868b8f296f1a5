"""Fleet-scale serving (ISSUE 9 surface).

Layers:

  * unit tests of the `FleetRouter` policies — affinity stickiness,
    ancestor-chain collapse, the load-pressure spill escape hatch,
    least-loaded balance, seeded random, and the deterministic
    ``("place", rid, node, reason)`` event log;
  * unit tests of the node-local KV model (`_LocalKV` token-LRU) and of
    the per-node prefetch mispredict-budget split
    (``PrefetchManager(n_nodes=)`` + ``note_node``);
  * an analytic `FleetSimulator` run showing prefix-affinity routing
    beating random placement on mean TTFT at 8 nodes under a Zipf
    prefix-trie workload (the bench acceptance gate, in miniature);
  * a mesh-sharded live engine run: per-shard fetch plans through the
    one controller, restored pages bit-identical to the unsharded
    engine, page arrays carrying a `NamedSharding`;
  * cross-environment replay (slow): `FleetSimulator` and the
    virtual-clock `LiveFleet` produce byte-identical router placement,
    fairness, and storage-cluster event logs over an 8-node Zipf-skewed
    script with a storage-node failure mid-trace (churn scripted by
    dispatch index, the env-invariant clock).
"""
import dataclasses

import numpy as np
import pytest

from repro.cluster.fairness import FairScheduler
from repro.cluster.fleet import (FLEET_POLICIES, FleetRouter,
                                 FleetSimulator, _LocalKV)
from repro.cluster.network import BandwidthTrace
from repro.cluster.simulator import MethodSpec, kvfetcher_spec
from repro.cluster.staging import HostStagingTier, PrefetchManager
from repro.cluster.storage import (StorageCluster, StorageNode,
                                   StoredPrefix, synthetic_stored_prefix)
from repro.core.scheduler import Request
from repro.data.workload import prefix_trie_specs, zipf_prefix_trace

MB = 1_000_000


def _req(rid, prefix=None, reuse=1_000, user=None, tier=None):
    return Request(rid=rid, arrival=0.0, prompt_len=reuse + 100,
                   reuse_tokens=reuse, prefix=prefix,
                   max_new_tokens=4, user=user, slo_tier=tier)


# ---------------------------------------------------------------------------
# router policies
# ---------------------------------------------------------------------------

def test_router_rejects_unknown_policy():
    with pytest.raises(AssertionError):
        FleetRouter(4, policy="round_robin")
    assert set(FLEET_POLICIES) == {"affinity", "least_loaded", "random"}


def test_affinity_is_sticky_and_logged():
    r = FleetRouter(8, policy="affinity")
    first = r.place(_req(0, prefix="p.hot"))
    for rid in range(1, 5):
        assert r.place(_req(rid, prefix="p.hot")) == first
    kinds = [reason for _, _, _, reason in r.events]
    assert kinds[0] == "hash" and all(k == "sticky" for k in kinds[1:])
    assert r.events[0] == ("place", 0, f"s{first}", "hash")


def test_affinity_replays_identically():
    def run():
        r = FleetRouter(8, policy="affinity")
        for rid, key in enumerate(["a", "b", "a", "c", "a", None, "b"]):
            r.place(_req(rid, prefix=key,
                         reuse=1_000 if key else 0))
        return r.events

    assert run() == run()


def test_affinity_collapses_ancestor_chains():
    """Every extension of a session chain routes to the chain root's
    node: the child's KV extends the parent's, so locality follows the
    trie, not the leaf key."""
    parents = {"root": None, "root.c": "root", "root.c.g": "root.c"}
    r = FleetRouter(8, policy="affinity", parent_of=parents.get)
    k_root = r.place(_req(0, prefix="root"))
    assert r.place(_req(1, prefix="root.c")) == k_root
    assert r.place(_req(2, prefix="root.c.g")) == k_root
    assert len(r.sticky) == 1  # one sticky entry for the whole chain


def test_affinity_no_prefix_falls_back_to_least_loaded():
    r = FleetRouter(4, policy="affinity")
    r.place(_req(0, prefix="p", reuse=1_000))
    k = r.place(_req(1, prefix=None, reuse=0))
    assert r.events[-1][3] == "least_loaded"
    assert r.assigned[k] == 1


def test_affinity_spills_under_load_pressure():
    """A single hot chain cannot pin the whole fleet's load on one
    node: once the sticky target runs past spill_factor x fair share
    (+ slack), the chain spills to the least-loaded node and re-sticks
    there."""
    r = FleetRouter(4, policy="affinity", spill_factor=1.0, spill_slack=2)
    k0 = r.place(_req(0, prefix="p.hot"))
    reasons = []
    for rid in range(1, 12):
        r.place(_req(rid, prefix="p.hot"))
        reasons.append(r.events[-1][3])
    assert "spill" in reasons
    first_spill = reasons.index("spill") + 1
    k1 = int(r.events[first_spill][2][1:])
    assert k1 != k0
    assert r.sticky["p.hot"] == int(r.events[-1][2][1:])
    # load never concentrates: max node share stays near the cap
    assert max(r.assigned) <= 1.0 * (sum(r.assigned) / 4) + 2 + 1


def test_least_loaded_balances_exactly():
    r = FleetRouter(4, policy="least_loaded")
    for rid in range(8):
        r.place(_req(rid, prefix="p.hot"))
    assert r.assigned == [2, 2, 2, 2]
    assert all(reason == "least_loaded" for *_, reason in r.events)


def test_random_is_seeded_by_rid_not_order():
    a = FleetRouter(8, policy="random")
    b = FleetRouter(8, policy="random")
    pa = [a.place(_req(rid)) for rid in range(16)]
    pb = [b.place(_req(rid)) for rid in reversed(range(16))]
    assert pa == list(reversed(pb))  # pure function of rid
    assert len(set(pa)) > 1  # actually spreads


# ---------------------------------------------------------------------------
# node-local KV model
# ---------------------------------------------------------------------------

def test_local_kv_lru_evicts_by_token_capacity():
    kv = _LocalKV(100)
    kv.put("a", 40)
    kv.put("b", 40)
    assert kv.hit("a", 40) and kv.hit("b", 40)
    assert not kv.hit("a", 41)  # insufficient coverage is a miss
    kv.hit("a", 40)  # touch: b becomes LRU
    kv.put("c", 40)  # over capacity -> evicts b
    assert kv.hit("a", 40) and kv.hit("c", 40) and not kv.hit("b", 1)
    assert kv.resident_tokens == 80
    kv.put("huge", 1_000)  # larger than capacity: never admitted
    assert not kv.hit("huge", 1)


# ---------------------------------------------------------------------------
# per-node prefetch budget split
# ---------------------------------------------------------------------------

def test_prefetch_budget_splits_per_node():
    """With n_nodes=4 each serving node may burn budget/4: one node's
    cold working set cannot exhaust speculation for the whole fleet."""
    entries = [StoredPrefix(key=k, n_tokens=1_000,
                            bytes_by_resolution={"240p": 10 * MB},
                            raw_kv_bytes=80 * MB)
               for k in ("p.a", "p.b")]
    cluster = StorageCluster([StorageNode("n0")])
    for e in entries:
        cluster.register(e, 0.0)
    pm = PrefetchManager(cluster, HostStagingTier(None),
                         mispredict_budget_bytes=40 * MB,
                         transport="sync", n_nodes=4)
    pm.note_node("p.a", "s0")
    pm.note_node("p.b", "s1")
    # s0 burns past its 10 MB share: p.a declined, s1's p.b untouched
    pm._account_waste("p.a", 12 * MB)
    assert pm.wasted_by_node == {"s0": 12 * MB}
    assert pm._over_budget("p.a") and not pm._over_budget("p.b")
    assert pm.request_prefetch("p.a", 0.0) is False
    assert pm.events[-1] == ("budget_reject", "p.a")
    # single-node fleets keep the flat global budget semantics
    pm_flat = PrefetchManager(cluster, HostStagingTier(None),
                              mispredict_budget_bytes=40 * MB,
                              transport="sync")
    pm_flat.note_node("p.a", "s0")
    pm_flat._account_waste("p.a", 12 * MB)
    assert not pm_flat._over_budget("p.a")


# ---------------------------------------------------------------------------
# analytic fleet: affinity beats random under Zipf (bench gate, small)
# ---------------------------------------------------------------------------

def _fleet_run(cfg, policy, specs, ratios):
    nodes = [StorageNode(f"n{i}", link=BandwidthTrace.constant(4.0))
             for i in range(3)]
    cluster = StorageCluster(nodes, replication=2)
    for sp in specs:
        cluster.register(synthetic_stored_prefix(
            sp.key, sp.n_tokens,
            raw_bytes_per_token=cfg.kv_bytes_per_token(),
            ratios=ratios, parent=sp.parent), 0.0)
    rng = np.random.default_rng(42)
    reqs = zipf_prefix_trace(rng, specs, n_requests=24, alpha=1.1,
                             gap=5.0, max_new_tokens=4)
    fleet = FleetSimulator(cfg, kvfetcher_spec(ratios), n_nodes=8,
                           bandwidth=BandwidthTrace.constant(8.0),
                           storage=cluster, policy=policy,
                           local_kv_tokens=150_000)
    return fleet.run(reqs, max_new_tokens=4)


def test_fleet_affinity_beats_random_on_mean_ttft():
    from repro.configs import get_config

    cfg = get_config("yi-34b")
    ratios = {"240p": 9.0, "480p": 8.5, "640p": 8.0, "1080p": 7.0}
    specs = prefix_trie_specs(4, 2)
    out = {}
    for policy in ("affinity", "random"):
        res = _fleet_run(cfg, policy, specs, ratios)
        tt = [r.ttft for r in res.requests]
        assert all(t is not None for t in tt)
        out[policy] = (float(np.mean(tt)), res)
    t_aff, res_aff = out["affinity"]
    t_rand, res_rand = out["random"]
    assert t_aff < t_rand, (t_aff, t_rand)
    assert res_aff.local_hits > res_rand.local_hits
    # the placement log covers every request, in arrival order
    assert [rid for _, rid, _, _ in res_aff.router_events] == \
        [r.rid for r in res_aff.requests]
    assert all(ev[0] == "place" and ev[2].startswith("s")
               for ev in res_aff.router_events)
    # every placed request was dispatched on its placed node
    assert set(res_aff.placements) == {r.rid for r in res_aff.requests}


# ---------------------------------------------------------------------------
# mesh-sharded live engine
# ---------------------------------------------------------------------------

def test_mesh_sharded_engine_matches_unsharded(tiny_cfg, tiny_params,
                                               donor_kv):
    """Per-shard fetch plans through the ONE controller: the sharded
    engine's logits match the unsharded engine's within the sharding
    tolerance at every step, and its page arrays carry a NamedSharding
    laid out by the logical-axis rules."""
    from jax.sharding import NamedSharding

    from repro.cluster.costmodel import CHIPS, EngineCostModel
    from repro.launch.mesh import make_debug_mesh
    from repro.serving.engine import LiveEngine
    from repro.serving.verify import (LogitsRecorder, check_streams,
                                      shard_logit_tolerance)
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    toks = rng.integers(0, tiny_cfg.vocab_size, 48)
    suffix = rng.integers(0, tiny_cfg.vocab_size, 8)
    kv_k, kv_v = donor_kv(toks)
    trace = BandwidthTrace.constant(0.01)

    def build():
        cluster = StorageCluster([StorageNode("n0")])
        cluster.register_prefix(toks, kv_k, kv_v, tokens_per_chunk=16,
                                resolutions=("240p",))
        return cluster, list(cluster.catalog)[0]

    def run(mesh, mesh_shards):
        cluster, key = build()
        logits = LogitsRecorder()
        eng = LiveEngine(tiny_params, tiny_cfg, cluster, on_logits=logits,
                         policy="kvfetcher", fetch_mode="sync",
                         bandwidth=trace, adaptive=False,
                         resolution="240p", resolutions=("240p",),
                         cost=EngineCostModel(tiny_cfg, CHIPS["h20"], 2),
                         mesh=mesh, mesh_shards=mesh_shards)
        req = eng.submit(np.concatenate([toks, suffix]),
                         reuse_prefix=key, reuse_tokens=48,
                         max_new_tokens=4)
        eng.run()
        return eng, req, logits

    base_eng, base_req, base_logits = run(None, None)
    mesh = make_debug_mesh(shape=(1, 1))
    shard_eng, shard_req, shard_logits = run(mesh, 3)
    assert shard_eng.n_shards == 3
    assert shard_req.fetch_done is not None and shard_req.storage_hit == \
        base_req.storage_hit == "full"
    tol = shard_logit_tolerance(tiny_cfg.num_layers, jnp.float32)
    check_streams(
        shard_logits[shard_req.rid], shard_eng.outputs[shard_req.rid],
        base_logits[base_req.rid], base_eng.outputs[base_req.rid],
        tol, "sharded vs unsharded")
    assert not shard_eng._sharded  # all shards completed and untracked
    assert isinstance(shard_eng.cache.k_pages.sharding, NamedSharding)
    assert isinstance(shard_eng.cache.v_pages.sharding, NamedSharding)


# ---------------------------------------------------------------------------
# cross-environment replay determinism (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_replay_identical_in_simulator_and_live_fleet(
        tiny_cfg, tiny_params, donor_kv):
    """ISSUE 9 acceptance: an 8-node fleet over a Zipf-skewed script
    with one storage node failing mid-trace replays byte-identical
    router placement, fairness, and storage-cluster lookup logs in the
    analytic `FleetSimulator` and the virtual-clock `LiveFleet`.
    Placement, local-KV residency, fair dispatch, and churn (scripted
    by dispatch index) are all pure functions of the request sequence,
    so the logs must match tuple for tuple.

    Script discipline (same as the ISSUE 8 cross-env test): a key that
    misses is never asked again — delayed write-on-miss re-admission
    fires at the fallback prefill's first token, a *clock*-dependent
    instant, so a later re-ask would race the re-admission differently
    in each environment.  The hot key's storage node dies right after
    its first fetch instead: every later ask serves from the serving
    node's LOCAL copy (no storage lookup at all), which is exactly the
    affinity-survives-churn win the router is for."""
    from repro.cluster.costmodel import CHIPS, EngineCostModel
    from repro.cluster.fleet import LiveFleet
    from repro.core.adaptive import DecodeTable

    TABLE = DecodeTable(name="fleet-toy", n_decoders=1,
                        latency={"240p": (0.06,)}, penalty={"240p": 0.0},
                        chunk_size_mb={"240p": 0.002})
    trace = BandwidthTrace.constant(0.0006)  # 75 kB/s
    N_NODES = 8
    LOCAL_TOKENS = 128
    # admission events ride on recompute_done (a clock), so only the
    # dispatch-ordered kinds are replay-comparable
    LOOKUP_KINDS = ("full", "partial", "miss", "fail", "recover",
                    "replicate")

    rng = np.random.default_rng(12)
    tok = {"a": rng.integers(0, tiny_cfg.vocab_size, 48),
           "b": rng.integers(0, tiny_cfg.vocab_size, 48),
           "c": rng.integers(0, tiny_cfg.vocab_size, 64)}
    suffix = rng.integers(0, tiny_cfg.vocab_size, 8)
    # drawn after suffix: lands on the same storage node as "a" for
    # this seed (asserted below — the churn must be visible)
    tok["d"] = rng.integers(0, tiny_cfg.vocab_size, 48)

    def build_cluster(live):
        nodes = [StorageNode("n0"), StorageNode("n1")]
        c = StorageCluster(nodes, replication=1, heal="manual")
        if live:
            for toks in tok.values():
                kv_k, kv_v = donor_kv(toks)
                c.register_prefix(toks, kv_k, kv_v, tokens_per_chunk=16,
                                  resolutions=("240p",))
        return c

    live_cluster = build_cluster(True)
    keys = list(live_cluster.catalog)  # [a, b, c, d] registration order
    by_name = dict(zip(tok, keys))
    # the HOT key's storage node dies after the very first dispatch:
    # every later "a" ask must serve from the serving node's local copy
    doomed = live_cluster.primary_node(by_name["a"]).node_id
    assert live_cluster.primary_node(by_name["d"]).node_id == doomed, \
        "d must share a's node or the churn is invisible; re-pick seed"
    assert all(live_cluster.primary_node(by_name[n]).node_id != doomed
               for n in ("b", "c")), "b/c must survive; re-pick seed"
    churn = [(1, "fail", doomed)]

    # (user, tier, name) in submit order — Zipf-skewed toward "a";
    # "d" is asked exactly once (it misses) and never again
    script = [("alice", "premium", "a"), ("bob", "standard", "b"),
              ("alice", "premium", "a"), ("mallory", "free", "c"),
              ("bob", "standard", "a"), ("alice", "premium", "b"),
              ("mallory", "free", "a"), ("bob", "standard", "c"),
              ("alice", "premium", "a"), ("mallory", "free", "d")]

    # -- live fleet (virtual clock, real engines) ------------------------
    fair_e = FairScheduler(max_inflight=1)
    fleet_e = LiveFleet(
        tiny_params, tiny_cfg, live_cluster, n_nodes=N_NODES,
        bandwidth=trace, policy="affinity", fairness=fair_e,
        local_kv_tokens=LOCAL_TOKENS, churn_at_dispatch=churn,
        engine_kw=dict(policy="kvfetcher", max_running=16,
                       decode_table=TABLE, use_table_sizes=True,
                       adaptive=False, resolution="240p",
                       resolutions=("240p",),
                       cost=EngineCostModel(tiny_cfg, CHIPS["h20"], 2)))
    for user, tier, name in script:
        fleet_e.submit(np.concatenate([tok[name], suffix]),
                       prefix_key=by_name[name],
                       reuse_tokens=len(tok[name]), max_new_tokens=2,
                       user=user, slo_tier=tier)
    fleet_e.run()

    # -- analytic simulator (synthetic twins, same virtual network) ------
    sim_cluster = build_cluster(False)
    for key in keys:
        src = live_cluster.catalog[key]
        sim_cluster.register(StoredPrefix(
            key=key, n_tokens=src.n_tokens,
            bytes_by_resolution={"240p": src.stored_bytes},
            raw_kv_bytes=src.raw_kv_bytes, parent=src.parent), 0.0)
    fair_s = FairScheduler(max_inflight=1)
    spec = MethodSpec("kvfetcher", ratios={"stream": 8.0}, adaptive=False,
                      fixed_resolution="240p", uses_decode_pool=True,
                      use_table_sizes=True, pipelined=False,
                      layerwise_admission=False, resolutions=("240p",))
    fleet_s = FleetSimulator(
        tiny_cfg, spec, n_nodes=N_NODES, bandwidth=trace,
        storage=sim_cluster, table=TABLE, fairness=fair_s,
        policy="affinity", local_kv_tokens=LOCAL_TOKENS,
        churn_at_dispatch=churn, chunk_tokens=16, max_running=16)
    reqs = [Request(rid=i, arrival=0.0,
                    prompt_len=len(tok[name]) + len(suffix),
                    reuse_tokens=len(tok[name]), prefix=by_name[name],
                    max_new_tokens=2, user=user, slo_tier=tier)
            for i, (user, tier, name) in enumerate(script)]
    res = fleet_s.run(reqs, max_new_tokens=2)

    # router placement replayed identically
    assert fleet_e.router.events == fleet_s.router.events
    assert res.router_events == fleet_s.router.events
    assert fleet_e.placement == fleet_s.placement
    # fairness decision log byte-identical
    assert fair_e.events == fair_s.events
    assert res.fairness_events == fair_s.events
    # storage tier saw the same dispatch-ordered churn/lookup sequence
    def lookups(cluster):
        return [e for e in cluster.events if e[0] in LOOKUP_KINDS]

    assert lookups(live_cluster) == lookups(sim_cluster)
    assert ("fail", "", doomed) in lookups(live_cluster)
    # every request served exactly once in both environments
    serves = [rid for _, rid, k, _ in fair_e.events if k == "serve"]
    assert sorted(serves) == list(range(len(script)))
    # the affinity win actually materialized: post-churn asks of the
    # hot key served from the serving node's local copy even though
    # its only storage replica is DEAD (identical count in both envs)
    live_locals = [r for e in fleet_e.engines for r in e.finished
                   if r.storage_hit == "local"]
    assert len(live_locals) == res.local_hits > 0
    assert any(r.prefix == by_name["a"] for r in live_locals)
    # ...and the storage failure really bit: the doomed-node key missed
    kinds = {k for _, _, k, _ in fair_e.events}
    assert "miss" in kinds
    assert {"arrive", "dispatch", "fetched", "serve"} <= kinds
    missed = {rid for _, rid, k, _ in fair_e.events if k == "miss"}
    assert missed == {9}  # the single "d" ask, and only it
    # real tokens came out of every live request
    for eng in fleet_e.engines:
        for r in eng.finished:
            assert len(fleet_e.engines[fleet_e.placement[r.rid]]
                       .outputs[r.rid]) == r.tokens_out
