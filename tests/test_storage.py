"""Multi-node prefix storage tier (ISSUE 3 acceptance surface).

Node-level tests cover byte-accurate capacity accounting and the three
eviction policies; cluster-level tests cover consistent-hash placement,
popularity replication, longest-prefix-match full/partial/miss
resolution, and determinism of the event log under a seeded Zipf
workload.  Integration tests drive the analytic simulator and the REAL
live engine and assert (a) a partial hit produces tokens identical to a
full recompute and (b) both environments replay the identical
hit/miss/evict event sequence for the same access order.
"""
import numpy as np
import pytest

from repro.core.scheduler import FetchingAwareScheduler, ReqState, Request
from repro.cluster.network import BandwidthTrace
from repro.cluster.storage import (KVStore, StorageCluster, StorageNode,
                                   StoredPrefix, synthetic_stored_prefix)
from repro.data.workload import prefix_trie_specs, zipf_prefix_trace

MB = 1_000_000


def _entry(key, n_tokens=1000, size=10 * MB, parent=None):
    return StoredPrefix(key=key, n_tokens=n_tokens,
                        bytes_by_resolution={"240p": size},
                        raw_kv_bytes=8 * size, parent=parent)


# ---------------------------------------------------------------------------
# StorageNode: capacity accounting + eviction policies
# ---------------------------------------------------------------------------

def test_node_capacity_accounting_per_resolution():
    n = StorageNode("n0", capacity_bytes=100 * MB)
    e = StoredPrefix("a", 100, {"240p": 10 * MB, "1080p": 30 * MB})
    assert n.put(e, 0.0) == (True, [])
    assert n.used_bytes == 40 * MB
    assert n.bytes_by_resolution == {"240p": 10 * MB, "1080p": 30 * MB}
    assert n.stored_bytes() == 40 * MB
    # eviction returns the bytes
    big = StoredPrefix("b", 100, {"240p": 70 * MB})
    ok, evicted = n.put(big, 1.0)
    assert ok and evicted == ["a"]
    assert n.used_bytes == 70 * MB
    assert n.bytes_by_resolution["1080p"] == 0


def test_node_rejects_entry_larger_than_capacity():
    n = StorageNode("n0", capacity_bytes=10 * MB)
    n.put(_entry("a", size=8 * MB), 0.0)
    ok, evicted = n.put(_entry("huge", size=20 * MB), 1.0)
    assert not ok and evicted == []  # never flushes the node for a lost cause
    assert n.contains("a") and n.stats.rejections == 1


def test_node_lru_evicts_least_recently_used():
    n = StorageNode("n0", capacity_bytes=30 * MB, policy="lru")
    for i, k in enumerate(("a", "b", "c")):
        n.put(_entry(k), float(i))
    n.get("a", 10.0)  # refresh a
    _, evicted = n.put(_entry("d"), 11.0)
    assert evicted == ["b"]  # oldest untouched


def test_node_lfu_keeps_frequent():
    n = StorageNode("n0", capacity_bytes=30 * MB, policy="lfu")
    for i, k in enumerate(("a", "b", "c")):
        n.put(_entry(k), float(i))
    for t in range(3):
        n.get("a", 10.0 + t)
    n.get("c", 20.0)  # recent but infrequent
    _, evicted = n.put(_entry("d"), 21.0)
    assert evicted == ["b"]  # 0 hits loses to recency


def test_node_cost_keeps_bytes_saved_per_byte_stored():
    """A proven-hot prefix survives a scan that flushes an LRU node."""
    seq = [("hot", 0.0)] + [(f"scan{i}", float(i + 1)) for i in range(3)]
    results = {}
    for policy in ("lru", "cost"):
        n = StorageNode("n0", capacity_bytes=30 * MB, policy=policy)
        n.put(_entry("hot"), 0.0)
        n.get("hot", 0.5)  # one hit: it has earned bytes-saved credit
        for key, t in seq[1:]:
            n.put(_entry(key), t)
        results[policy] = n.contains("hot")
    assert results["cost"] and not results["lru"]


def test_node_cost_prefers_small_high_value_entries():
    n = StorageNode("n0", capacity_bytes=30 * MB, policy="cost")
    small = StoredPrefix("small", 100, {"240p": 5 * MB},
                         raw_kv_bytes=50 * MB)
    big = StoredPrefix("big", 100, {"240p": 25 * MB}, raw_kv_bytes=50 * MB)
    n.put(small, 0.0)
    n.put(big, 1.0)
    n.get("small", 2.0)
    n.get("big", 3.0)  # equal hits; big saves fewer bytes per byte stored
    _, evicted = n.put(_entry("new", size=10 * MB), 4.0)
    assert evicted == ["big"]


def test_node_reregister_replaces_stale_entry():
    """Re-registering a resident key must swap in the new artifact and
    re-account its bytes (regression: the flat dict overwrote)."""
    n = StorageNode("n0", capacity_bytes=100 * MB)
    n.put(_entry("a", size=10 * MB), 0.0)
    n.get("a", 1.0)
    v2 = StoredPrefix("a", 1000, {"240p": 10 * MB, "480p": 15 * MB})
    ok, evicted = n.put(v2, 2.0)
    assert ok and not evicted
    assert n.residents["a"].entry is v2
    assert n.residents["a"].hits == 1  # same prefix: history kept
    assert n.used_bytes == 25 * MB
    assert n.bytes_by_resolution == {"240p": 10 * MB, "480p": 15 * MB}
    assert n.stats.admissions == 1  # replacement, not a new admission


def test_node_repr_is_human_readable():
    n = StorageNode("n0", capacity_bytes=2e9, policy="cost")
    n.put(_entry("a", size=500 * MB), 0.0)
    r = repr(n)
    assert "0.50/2.00 GB" in r and "cost" in r and "1 prefixes" in r
    assert "unbounded" in repr(StorageNode("n1"))


# ---------------------------------------------------------------------------
# StorageCluster: placement, replication, LPM lookup, determinism
# ---------------------------------------------------------------------------

def _cluster(n_nodes=3, cap=35 * MB, policy="lru", **kw):
    nodes = [StorageNode(f"n{i}", capacity_bytes=cap, policy=policy)
             for i in range(n_nodes)]
    return StorageCluster(nodes, **kw)


def test_consistent_hash_placement_deterministic_and_spread():
    keys = [f"k{i}" for i in range(60)]
    c1, c2 = _cluster(cap=None), _cluster(cap=None)
    assert [c1.primary_node(k).node_id for k in keys] == \
        [c2.primary_node(k).node_id for k in keys]
    used = {c1.primary_node(k).node_id for k in keys}
    assert used == {"n0", "n1", "n2"}  # all nodes take keys


def test_lookup_full_partial_miss_and_ancestor_chain():
    c = _cluster(n_nodes=1, cap=25 * MB)
    c.register(_entry("root", n_tokens=400, size=10 * MB), 0.0)
    c.register(_entry("child", n_tokens=600, size=10 * MB,
                      parent="root"), 1.0)
    full = c.lookup("child", 2.0)
    assert full.kind == "full" and full.covered_tokens == 600
    assert full.node.node_id == "n0"
    # make child the LRU victim, then squeeze it out
    c.lookup("root", 2.5)
    c.register(_entry("x", n_tokens=100, size=10 * MB), 3.0)
    assert not c.nodes[0].contains("child")
    assert c.nodes[0].contains("root")
    partial = c.lookup("child", 5.0)
    assert partial.kind == "partial"
    assert partial.entry.key == "root" and partial.covered_tokens == 400
    assert partial.requested_tokens == 600
    miss = c.lookup("never-registered", 6.0)
    assert miss.kind == "miss" and miss.entry is None


def test_write_on_miss_is_delayed_until_recompute_done():
    """A miss must NOT re-admit at lookup time — the recomputed KV only
    exists once the fallback prefill finishes (notify_recompute_done)."""
    c = _cluster(n_nodes=1, cap=25 * MB)
    c.register(_entry("a", size=10 * MB), 0.0)
    c.register(_entry("b", size=10 * MB), 1.0)
    c.register(_entry("c", size=10 * MB), 2.0)  # evicts a (lru)
    assert not c.nodes[0].contains("a")
    hit = c.lookup("a", 3.0)
    assert hit.kind == "miss" and hit.missed_key == "a"
    assert not c.nodes[0].contains("a")  # not yet: recompute in flight
    c.notify_recompute_done("a", 5.0)
    assert c.nodes[0].contains("a")  # pull-through re-admission
    assert c.lookup("a", 6.0).kind == "full"
    # idempotent: a second notify without a pending miss is a no-op
    n_events = len(c.events)
    c.notify_recompute_done("a", 7.0)
    assert len(c.events) == n_events


def test_popularity_replication_spreads_hot_prefixes():
    c = _cluster(cap=None, placement="popular", replicate_threshold=2)
    c.register(_entry("hot"), 0.0)
    c.register(_entry("cold"), 0.0)
    for t in range(3):
        assert c.lookup("hot", 1.0 + t).kind == "full"
    holders = [n.node_id for n in c.nodes if n.contains("hot")]
    assert len(holders) >= 2
    assert ("replicate", "hot", holders[-1]) in c.events or \
        any(ev[0] == "replicate" and ev[1] == "hot" for ev in c.events)
    assert sum(1 for n in c.nodes if n.contains("cold")) == 1


def test_lookup_tokens_longest_prefix_match():
    c = _cluster(cap=None)
    toks = np.arange(64)
    root = StoredPrefix("root", 32, {"240p": MB},
                        token_ids=toks[:32])
    child = StoredPrefix("child", 48, {"240p": MB}, parent="root",
                         token_ids=toks[:48])
    c.register(root, 0.0)
    c.register(child, 0.0)
    full = c.lookup_tokens(toks[:48], 1.0)
    assert full.kind == "full" and full.entry.key == "child"
    # longer ask than any stored prefix: partial on the deepest ancestor
    part = c.lookup_tokens(toks[:64], 2.0)
    assert part.kind == "partial" and part.entry.key == "child"
    assert part.covered_tokens == 48 and part.requested_tokens == 64
    # diverging tokens match nothing
    other = np.arange(100, 140)
    assert c.lookup_tokens(other, 3.0).kind == "miss"


def test_cluster_event_log_deterministic_under_seeded_zipf():
    """Same seed, same sizes -> byte-identical event logs, with real
    eviction pressure (the determinism the cross-env test relies on)."""
    specs = prefix_trie_specs(3, 2, base_tokens=400, ext_tokens=200)

    def run_once():
        c = _cluster(n_nodes=2, cap=25 * MB, policy="cost")
        for s in specs:
            c.register(_entry(s.key, n_tokens=s.n_tokens, size=10 * MB,
                              parent=s.parent), 0.0)
        rng = np.random.default_rng(42)
        reqs = zipf_prefix_trace(rng, specs, n_requests=30, alpha=1.2,
                                 gap=1.0)
        for r in reqs:
            c.lookup(r.prefix, r.arrival + 1.0,
                     requested_tokens=r.reuse_tokens)
        return list(c.events)

    e1, e2 = run_once(), run_once()
    assert e1 == e2
    assert any(ev[0] == "evict" for ev in e1), "no capacity pressure"
    assert any(ev[0] in ("full", "partial") for ev in e1)


def test_kvstore_facade_keeps_flat_api(synthetic_kv):
    kv_k, kv_v, toks = synthetic_kv(8, 3, 2, 4)
    store = KVStore()
    man = store.register_prefix(toks, kv_k, kv_v, tokens_per_chunk=4,
                                resolutions=("240p",))
    assert store.lookup(man.prefix) is man
    assert store.lookup("nope") is None
    ref = man.refs[0]
    assert store.get_chunk(man.prefix, ref.chunk_id, "240p") == \
        man.blobs[(ref.chunk_id, "240p")]
    assert store.stored_bytes() == sum(len(b) for b in man.blobs.values())


# ---------------------------------------------------------------------------
# fault tolerance: fail/recover, ring heal, TTL/pinning, admission (ISSUE 4)
# ---------------------------------------------------------------------------

def test_node_fail_loses_residents_and_recover_rejoins_empty():
    n = StorageNode("n0", capacity_bytes=100 * MB)
    n.put(_entry("a"), 0.0)
    n.put(_entry("b"), 1.0)
    lost = n.fail()
    assert lost == ["a", "b"] and not n.alive
    assert n.used_bytes == 0 and not n.residents
    assert n.stats.failures == 1
    assert "FAILED" in repr(n)
    n.recover()
    assert n.alive and not n.residents
    ok, _ = n.put(_entry("c"), 2.0)
    assert ok


def test_failed_node_leaves_the_ring():
    c = _cluster(cap=None)
    keys = [f"k{i}" for i in range(40)]
    n0_keys = [k for k in keys if c.primary_node(k).node_id == "n0"]
    assert n0_keys
    c.fail_node("n0", 0.0)
    assert ("fail", "", "n0") in c.events
    for k in n0_keys:  # keys re-route to their ring successors
        assert c.primary_node(k).node_id != "n0"
    c.recover_node("n0", 1.0)
    assert ("recover", "", "n0") in c.events
    assert c.primary_node(n0_keys[0]).node_id == "n0"


def test_ring_heal_restores_replication_from_surviving_replica():
    c = _cluster(cap=None, replication=2)
    c.register(_entry("k"), 0.0)
    holders = [n.node_id for n in c.nodes if n.contains("k")]
    assert len(holders) == 2  # replication=2 at registration
    c.fail_node(holders[0], 1.0)
    # sync heal: a new second replica appears immediately, sourced from
    # the survivor (the catalog is never needed while a replica lives)
    now_holders = [n.node_id for n in c.nodes if n.contains("k")]
    assert len(now_holders) == 2 and holders[0] not in now_holders
    assert ("heal", "k", [h for h in now_holders
                          if h != holders[1]][0]) in c.events
    assert c.lookup("k", 2.0).kind == "full"
    assert c.heals_completed == 1


def test_ring_heal_reseeds_unreplicated_key_from_catalog():
    c = _cluster(cap=None, replication=1)
    c.register(_entry("k"), 0.0)
    holder = next(n.node_id for n in c.nodes if n.contains("k"))
    c.fail_node(holder, 1.0)
    assert sum(1 for n in c.nodes if n.contains("k")) == 1
    assert any(e[0] == "heal" and e[1] == "k" for e in c.events)
    assert c.lookup("k", 2.0).kind == "full"


def test_fail_node_does_not_count_expired_copies_as_survivors():
    """A TTL-stale replica is not a heal source: failing one holder of
    a fully-expired pair must re-seed from the catalog (and log the
    expiry), not under-replicate against a ghost copy."""
    c = _cluster(cap=None, replication=2)
    c.register(StoredPrefix("k", 1000, {"240p": MB}, raw_kv_bytes=8 * MB,
                            ttl=5.0), 0.0)
    holders = [n.node_id for n in c.nodes if n.contains("k")]
    assert len(holders) == 2
    c.fail_node(holders[0], 100.0)  # both copies are long expired
    assert any(e == ("expire", "k", holders[1]) for e in c.events)
    live = [n.node_id for n in c.nodes if n.contains("k")]
    assert len(live) == 2 and holders[0] not in live  # fully re-seeded
    assert c.lookup("k", 101.0).kind == "full"


def test_rejected_heal_is_not_counted_completed():
    """A heal whose target cannot take the entry (pinned-full node)
    logs a reject and must NOT bump heals_completed — the replication
    factor was not restored."""
    c = _cluster(n_nodes=2, cap=15 * MB, replication=1)
    c.register(_entry("k"), 0.0)
    holder = next(n for n in c.nodes if n.contains("k"))
    other = next(n for n in c.nodes if n is not holder)
    other.put(StoredPrefix("pin", 100, {"240p": 10 * MB}, pinned=True),
              0.5)
    c.fail_node(holder.node_id, 1.0)
    assert c.heals_completed == 0
    assert ("reject", "k", other.node_id) in c.events
    assert not other.contains("k")


def test_manual_heal_queues_until_pumped():
    c = _cluster(cap=None, replication=1, heal="manual")
    c.register(_entry("k"), 0.0)
    holder = next(n.node_id for n in c.nodes if n.contains("k"))
    c.fail_node(holder, 1.0)
    assert not any(n.contains("k") for n in c.nodes)
    assert c.lookup("k", 2.0).kind == "miss"  # down until pumped
    assert c.pump_heal(3.0) == 1
    assert c.lookup("k", 4.0).kind == "full"


def test_ttl_expires_lazily_at_lookup():
    c = _cluster(n_nodes=1, cap=None)
    c.register(StoredPrefix("short", 1000, {"240p": MB}, ttl=10.0), 0.0)
    assert c.lookup("short", 5.0).kind == "full"  # inside TTL
    hit = c.lookup("short", 20.0)  # stale: dropped at this lookup
    assert hit.kind == "miss"
    assert ("expire", "short", "n0") in c.events
    assert c.nodes[0].stats.expirations == 1


def test_ttl_swept_eagerly_at_eviction_scan():
    n = StorageNode("n0", capacity_bytes=30 * MB)
    n.put(StoredPrefix("stale", 1000, {"240p": 20 * MB}, ttl=5.0), 0.0)
    n.put(_entry("live"), 1.0)
    # at t=10 "stale" is expired: the scan reclaims it instead of
    # evicting the live entry
    ok, evicted = n.put(_entry("new"), 10.0)
    assert ok and evicted == []
    assert not n.contains("stale") and n.contains("live")
    assert n.stats.expirations == 1 and n.stats.evictions == 0


def test_reput_refreshes_ttl_clock():
    n = StorageNode("n0", capacity_bytes=None)
    e = StoredPrefix("k", 1000, {"240p": MB}, ttl=10.0)
    n.put(e, 0.0)
    n.put(e, 8.0)  # re-admission restarts the clock
    assert not n.is_expired("k", 15.0)
    assert n.is_expired("k", 19.0)


def test_pinned_survives_eviction_and_never_expires():
    n = StorageNode("n0", capacity_bytes=30 * MB, policy="lru")
    n.put(StoredPrefix("pin", 1000, {"240p": 10 * MB}, pinned=True,
                       ttl=1.0), 0.0)
    for i in range(4):  # scan pressure that flushes everything unpinned
        n.put(_entry(f"scan{i}"), 100.0 + i)
    assert n.contains("pin")  # neither evicted nor expired (ttl ignored)
    assert not n.is_expired("pin", 1e9)


def test_pinned_full_node_rejects_instead_of_unpinning():
    n = StorageNode("n0", capacity_bytes=30 * MB)
    n.put(StoredPrefix("p1", 1000, {"240p": 15 * MB}, pinned=True), 0.0)
    n.put(StoredPrefix("p2", 1000, {"240p": 10 * MB}, pinned=True), 1.0)
    ok, evicted = n.put(_entry("x"), 2.0)  # 10 MB cannot fit beside pins
    assert not ok and evicted == []
    assert n.stats.rejections == 1
    assert n.contains("p1") and n.contains("p2")


def test_admission_second_hit_defers_residency():
    c = _cluster(n_nodes=1, cap=None, admission="second_hit",
                 admission_min_asks=2)
    c.register(_entry("a"), 0.0)
    assert ("reject", "a", "") in c.events  # cataloged, not resident
    assert not c.nodes[0].contains("a")
    assert c.lookup("a", 1.0).kind == "miss"  # ask 1
    c.notify_recompute_done("a", 2.0)
    assert not c.nodes[0].contains("a")  # 1 ask < 2: still filtered
    assert c.lookup("a", 3.0).kind == "miss"  # ask 2
    c.notify_recompute_done("a", 4.0)
    assert c.nodes[0].contains("a")  # earned residency
    assert c.lookup("a", 5.0).kind == "full"


def test_admission_cost_threshold_filters_low_value_entries():
    c = _cluster(n_nodes=1, cap=None, admission="cost",
                 admission_min_score=4.0)
    # raw/stored = 8 -> one ask scores 8 >= 4; a no-compression entry
    # (raw == stored) scores 1 per ask and needs 4 asks
    c.register(_entry("dense"), 0.0)
    cheap = StoredPrefix("cheap", 1000, {"240p": 10 * MB},
                         raw_kv_bytes=10 * MB)
    c.register(cheap, 0.0)
    for t in range(2):
        c.lookup("dense", 1.0 + t)
        c.lookup("cheap", 1.5 + t)
    c.notify_recompute_done("dense", 4.0)
    c.notify_recompute_done("cheap", 4.0)
    assert c.nodes[0].contains("dense")
    assert not c.nodes[0].contains("cheap")


def test_heal_bypasses_admission_control():
    c = _cluster(cap=None, replication=1, admission="second_hit",
                 admission_min_asks=2)
    c.register(_entry("k"), 0.0)
    for t in range(2):
        c.lookup("k", 1.0 + t)
    c.notify_recompute_done("k", 3.0)
    holder = next(n.node_id for n in c.nodes if n.contains("k"))
    c.fail_node(holder, 4.0)
    # the heal restores residency even though asks reset nothing —
    # admission gates *new* writes, not recovery of granted ones
    assert any(n.contains("k") for n in c.nodes)


# ---------------------------------------------------------------------------
# scheduler handoff
# ---------------------------------------------------------------------------

def test_notify_fetch_miss_requeues_as_plain_prefill():
    sched = FetchingAwareScheduler("kvfetcher", max_running=4)
    req = Request(rid=0, arrival=0.0, prompt_len=1000, reuse_tokens=900,
                  prefix="p")
    sched.submit(req, 0.0)
    sched.schedule(0.0)
    assert req.state is ReqState.WAITING_FOR_KV
    (fr,) = sched.take_fetches()
    sched.notify_fetch_miss(fr, 1.0)
    assert req.reuse_tokens == 0 and req.requested_reuse_tokens == 900
    assert req.storage_hit == "miss"
    assert req.state is ReqState.WAITING and not req.needs_fetch
    (adm,) = sched.schedule(1.0)
    assert adm is req


def test_notify_fetch_miss_unblocks_fetch_agnostic_head():
    sched = FetchingAwareScheduler("fetch_agnostic", max_running=4)
    head = Request(rid=0, arrival=0.0, prompt_len=1000, reuse_tokens=900,
                   prefix="p")
    tail = Request(rid=1, arrival=0.0, prompt_len=100)
    sched.submit(head, 0.0)
    sched.submit(tail, 0.0)
    assert sched.schedule(0.0) == []  # head blocks (HOL)
    sched.take_fetches()
    sched.notify_fetch_miss(head, 1.0)
    assert sched.schedule(1.0) == [head, tail]


# ---------------------------------------------------------------------------
# simulator integration
# ---------------------------------------------------------------------------

def _sim(storage, requests, **kw):
    from repro.configs import get_config
    from repro.core.adaptive import H20_TABLE
    from repro.cluster.simulator import ServingSimulator, kvfetcher_spec

    cfg = get_config("yi-34b")
    ratios = {"240p": 9.0, "480p": 8.5, "640p": 8.0, "1080p": 7.0}
    sim = ServingSimulator(cfg, kvfetcher_spec(ratios), chip="h20",
                           n_chips=2,
                           bandwidth=BandwidthTrace.constant(8.0),
                           storage=storage, table=H20_TABLE, **kw)
    return sim.run(requests, max_new_tokens=4), cfg


def _sim_cluster(cfg, specs, *, n_nodes=3, cap_fraction=None,
                 policy="lru", gbps=8.0, **kw):
    """Cluster of synthetic entries; each node's capacity is
    ``cap_fraction`` of the library's total bytes (None = unbounded)."""
    ratios = {"240p": 9.0, "480p": 8.5, "640p": 8.0, "1080p": 7.0}
    entries = [synthetic_stored_prefix(
        s.key, s.n_tokens, raw_bytes_per_token=cfg.kv_bytes_per_token(),
        ratios=ratios, parent=s.parent) for s in specs]
    total = sum(e.stored_bytes for e in entries)
    cap = None if cap_fraction is None else int(total * cap_fraction)
    nodes = [StorageNode(f"n{i}", capacity_bytes=cap, policy=policy,
                         link=BandwidthTrace.constant(gbps))
             for i in range(n_nodes)]
    cluster = StorageCluster(nodes, **kw)
    for e in entries:
        cluster.register(e, 0.0)
    return cluster


def test_sim_full_partial_miss_paths_complete():
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(2, 2, base_tokens=40_000, ext_tokens=20_000)
    cluster = _sim_cluster(cfg, specs)
    # evict exactly one child so its request becomes a partial hit
    child = specs[1].key
    node = next(n for n in cluster.nodes if n.contains(child))
    node._drop(child)
    reqs = [
        Request(rid=0, arrival=10.0, prompt_len=41_000,
                reuse_tokens=40_000, prefix=specs[0].key),  # full
        Request(rid=1, arrival=200.0, prompt_len=61_000,
                reuse_tokens=60_000, prefix=child),         # partial
        Request(rid=2, arrival=400.0, prompt_len=61_000,
                reuse_tokens=60_000, prefix="unknown"),     # miss
    ]
    res, _ = _sim(cluster, reqs)
    assert [r.storage_hit for r in reqs] == ["full", "partial", "miss"]
    assert all(r.t_first_token is not None for r in reqs)
    part = reqs[1]
    assert part.reuse_tokens == 40_000  # ancestor coverage
    assert part.requested_reuse_tokens == 60_000
    assert part.storage_node == node.node_id or part.storage_node
    miss = reqs[2]
    assert miss.reuse_tokens == 0 and not miss.needs_fetch
    # a miss pays full prefill: slowest TTFT of the three
    assert miss.ttft > part.ttft > reqs[0].ttft


def test_sim_fetch_routes_over_storage_node_link():
    """Same request, same default link — only the storage node's own
    link differs, so the TTFT gap proves per-node routing."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(1, 1, base_tokens=50_000)
    ttfts = {}
    for gbps in (16.0, 1.0):
        cluster = _sim_cluster(cfg, specs, gbps=gbps)
        req = Request(rid=0, arrival=1.0, prompt_len=51_000,
                      reuse_tokens=50_000, prefix=specs[0].key)
        _sim(cluster, [req])
        ttfts[gbps] = req.ttft
    assert ttfts[1.0] > 2.0 * ttfts[16.0]


def test_sim_eviction_policies_diverge_and_are_deterministic():
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(3, 2, base_tokens=40_000,
                              ext_tokens=20_000)
    hits = {}
    events = {}
    for policy in ("lru", "cost"):
        runs = []
        for _ in range(2):
            cluster = _sim_cluster(cfg, specs, n_nodes=1,
                                   cap_fraction=0.35, policy=policy)
            rng = np.random.default_rng(42)
            reqs = zipf_prefix_trace(rng, specs, n_requests=30,
                                     alpha=1.1, gap=120.0,
                                     max_new_tokens=4)
            _sim(cluster, reqs)
            runs.append(list(cluster.events))
            hits[policy] = cluster.hit_rate()
        assert runs[0] == runs[1], f"{policy} events nondeterministic"
        events[policy] = runs[0]
        assert any(e[0] == "evict" for e in runs[0])
    assert events["lru"] != events["cost"]
    # the cost policy retains proven-hot prefixes the LRU flushes
    assert hits["cost"] > hits["lru"]


def test_sim_scripted_failure_unreplicated_pays_full_prefill():
    """fail_at= kills the only holder mid-trace: the next ask misses
    (full-prefill TTFT), the link heal lands *after* that miss (heal
    traffic is not teleportation), and a later ask hits again."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(2, 1, base_tokens=40_000)
    cluster = _sim_cluster(cfg, specs, n_nodes=3, replication=1,
                           heal="link")
    victim = cluster.primary_node(specs[0].key).node_id
    reqs = [
        Request(rid=0, arrival=10.0, prompt_len=41_000,
                reuse_tokens=40_000, prefix=specs[0].key),  # pre-fail
        Request(rid=1, arrival=301.0, prompt_len=41_000,
                reuse_tokens=40_000, prefix=specs[0].key),  # mid-heal
        Request(rid=2, arrival=900.0, prompt_len=41_000,
                reuse_tokens=40_000, prefix=specs[0].key),  # healed
    ]
    res, _ = _sim(cluster, reqs, fail_at=[(300.0, victim)])
    assert [r.storage_hit for r in reqs] == ["full", "miss", "full"]
    assert reqs[1].ttft > 2.0 * reqs[0].ttft  # miss pays the prefill
    kinds = [e[0] for e in cluster.events]
    assert "fail" in kinds and "heal" in kinds
    # the heal completed over the wire, strictly after rid=1's miss
    miss_i = cluster.events.index(("miss", specs[0].key, ""))
    heal_i = next(i for i, e in enumerate(cluster.events)
                  if e[0] == "heal" and e[1] == specs[0].key)
    assert heal_i > miss_i
    assert res.requests  # completed trace


def test_sim_replicated_cluster_serves_through_failure():
    """With replication=2 the surviving replica absorbs the failure:
    the post-fail ask is still a full hit at near-identical TTFT."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(2, 1, base_tokens=40_000)
    cluster = _sim_cluster(cfg, specs, n_nodes=3, replication=2,
                           heal="link")
    holders = [n.node_id for n in cluster.nodes
               if n.contains(specs[0].key)]
    assert len(holders) == 2
    # rid=1 lands while the heal still streams over the survivor's link
    # (contention, not failure, is its penalty); rid=2/3 land after
    reqs = [Request(rid=i, arrival=t, prompt_len=41_000,
                    reuse_tokens=40_000, prefix=specs[0].key)
            for i, t in enumerate((10.0, 301.0, 450.0, 600.0))]
    _sim(cluster, reqs, fail_at=[(300.0, holders[0])])
    assert [r.storage_hit for r in reqs] == ["full"] * 4
    assert all(r.storage_node != holders[0] for r in reqs[1:])
    post = [r.ttft for r in reqs[1:]]
    assert sum(post) / len(post) < 1.3 * reqs[0].ttft
    # the mid-heal request pays heal contention; the healed ones do not
    assert reqs[1].ttft > reqs[2].ttft
    assert reqs[2].ttft < 1.1 * reqs[0].ttft


def test_churn_schedule_is_seeded_and_replayable():
    from repro.data.workload import churn_schedule
    ids = ["n0", "n1", "n2"]
    s1 = churn_schedule(np.random.default_rng(3), ids, n_failures=3,
                        t_start=100.0, gap=400.0, downtime=200.0)
    s2 = churn_schedule(np.random.default_rng(3), ids, n_failures=3,
                        t_start=100.0, gap=400.0, downtime=200.0)
    assert s1 == s2  # same seed -> same trace in every environment
    fail_at, recover_at = s1
    assert [t for t, _ in fail_at] == [100.0, 500.0, 900.0]
    assert [t for t, _ in recover_at] == [300.0, 700.0, 1100.0]
    assert all(nid in ids for _, nid in fail_at)
    # downtime=None: failed nodes stay down, and the schedule never
    # kills the last alive node (fail_node requires a survivor)
    fails, recs = churn_schedule(np.random.default_rng(3), ["n0", "n1"],
                                 n_failures=5, downtime=None)
    assert recs == [] and len(fails) == 1


def test_sim_churned_node_recovers_and_rejoins_the_ring():
    """A full fail->recover cycle mid-trace: requests keep being served
    (replica during the outage), and after recovery the ring routes the
    key's primary back to the recovered node."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(1, 1, base_tokens=40_000)
    cluster = _sim_cluster(cfg, specs, n_nodes=3, replication=2)
    victim = cluster.primary_node(specs[0].key).node_id
    reqs = [Request(rid=i, arrival=t, prompt_len=41_000,
                    reuse_tokens=40_000, prefix=specs[0].key)
            for i, t in enumerate((10.0, 350.0, 700.0))]
    _sim(cluster, reqs, fail_at=[(300.0, victim)],
         recover_at=[(600.0, victim)])
    assert [r.storage_hit for r in reqs] == ["full"] * 3
    kinds = [e[0] for e in cluster.events]
    assert "fail" in kinds and "recover" in kinds
    assert cluster.by_id[victim].alive
    assert cluster.primary_node(specs[0].key).node_id == victim


def test_sim_churn_scheduled_after_last_arrival_still_executes():
    """fail/recover instants after the final request must still fire —
    the post-run cluster state has to be honest."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(1, 1, base_tokens=40_000)
    cluster = _sim_cluster(cfg, specs, n_nodes=3, replication=2)
    victim = cluster.primary_node(specs[0].key).node_id
    reqs = [Request(rid=0, arrival=10.0, prompt_len=41_000,
                    reuse_tokens=40_000, prefix=specs[0].key)]
    _sim(cluster, reqs, fail_at=[(500.0, victim)],
         recover_at=[(600.0, victim)])
    kinds = [e[0] for e in cluster.events]
    assert "fail" in kinds and "recover" in kinds
    assert cluster.by_id[victim].alive


def test_recovery_rebalance_moves_key_home_and_trims_surplus():
    """Recovery re-balance (ISSUE bugfix): before the fix a recovered
    node rejoined the ring empty and its keys stayed on the heal
    survivor forever; now recovery streams them back (``rebalance``
    events) and trims the surplus copy (``rebalance_drop``), restoring
    replication-factor occupancy."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(1, 1, base_tokens=40_000)
    cluster = _sim_cluster(cfg, specs, n_nodes=2, replication=1,
                           heal="sync")
    key = specs[0].key
    home = cluster.primary_node(key)
    other = next(n for n in cluster.nodes if n is not home)
    assert home.contains(key) and not other.contains(key)
    cluster.fail_node(home.node_id, 10.0)  # sync heal -> other
    assert other.contains(key)
    cluster.recover_node(home.node_id, 20.0)
    assert ("rebalance", key, home.node_id) in cluster.events
    assert ("rebalance_drop", key, other.node_id) in cluster.events
    assert home.contains(key) and not other.contains(key)
    assert cluster.rebalances_completed == 1
    assert cluster.heals_completed == 1  # the fail-time heal, untouched


def test_rtt_aware_replica_rotation_excludes_slow_node():
    """RTT-aware replica selection (ISSUE bugfix): with no RTT samples
    the rotation is the legacy round-robin over all residents; once a
    replica's observed RTT drifts beyond the slack band it drops out of
    the rotation while the near-tied fast replicas keep sharing load."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(1, 1, base_tokens=40_000)
    cluster = _sim_cluster(cfg, specs, n_nodes=3, replication=3)
    key, n_tok = specs[0].key, specs[0].n_tokens

    def served(n_lookups):
        start = len(cluster.events)
        for _ in range(n_lookups):
            hit = cluster.lookup(key, 0.0, requested_tokens=n_tok)
            assert hit.kind == "full"
        return [e[2] for e in cluster.events[start:] if e[0] == "full"]

    all_ids = {n.node_id for n in cluster.nodes}
    assert set(served(3)) == all_ids  # legacy: everyone rotates
    fast = sorted(all_ids)[:2]
    slow = next(iter(all_ids - set(fast)))
    for nid in fast:
        cluster.observe_rtt(nid, 0.010)
    cluster.observe_rtt(slow, 0.200)  # way past the 25% slack band
    got = served(4)
    assert slow not in got, "slow replica still in the rotation"
    assert set(got) == set(fast), "fast replicas must share the load"
    # uniform samples restore the full rotation (slack band keeps
    # near-tied nodes in) — selection stays a pure access-seq function
    cluster.node_rtt = {nid: 0.010 for nid in all_ids}
    assert set(served(3)) == all_ids


def test_rtt_aware_heal_source_prefers_fast_holder():
    """Heal/re-balance source selection (ISSUE bugfix): the source is
    the lowest observed-RTT surviving holder; with no samples it stays
    the legacy first-in-ring-order survivor."""
    from repro.configs import get_config
    cfg = get_config("yi-34b")
    specs = prefix_trie_specs(1, 1, base_tokens=40_000)
    key = specs[0].key

    def queued_source(rtts):
        cluster = _sim_cluster(cfg, specs, n_nodes=4, replication=3,
                               heal="manual")
        for nid, rtt in rtts.items():
            cluster.observe_rtt(nid, rtt)
        ring = cluster._ring_nodes(key)
        cluster.fail_node(ring[0].node_id, 10.0)
        (entry, source_id, target_id, kind), = cluster.heal_queue
        assert kind == "heal" and entry.key == key
        assert target_id == ring[3].node_id  # the non-holder successor
        return source_id, ring

    source_id, ring = queued_source({})
    assert source_id == ring[1].node_id  # legacy: first survivor
    source_id, ring = queued_source({ring[1].node_id: 0.300,
                                     ring[2].node_id: 0.020})
    assert source_id == ring[2].node_id  # RTT overrides ring order


# ---------------------------------------------------------------------------
# live engine integration (real model, real codec)
# ---------------------------------------------------------------------------

def _live_cluster(donor_kv, token_sets, *, cap=None, policy="lru",
                  n_nodes=1, **cluster_kw):
    nodes = [StorageNode(f"n{i}", capacity_bytes=cap, policy=policy)
             for i in range(n_nodes)]
    cluster = StorageCluster(nodes, **cluster_kw)
    for toks in token_sets:
        kv_k, kv_v = donor_kv(toks)
        cluster.register_prefix(toks, kv_k, kv_v, tokens_per_chunk=16,
                                resolutions=("240p",))
    return cluster


def test_live_partial_hit_matches_full_recompute(tiny_cfg, tiny_params,
                                                 donor_kv):
    """Acceptance: ancestor fetch + tail recompute gives the logits of a
    full recompute of the same prompt, within the int8-KV tolerance."""
    from repro.serving.engine import LiveEngine
    from repro.serving.verify import (LogitsRecorder, check_streams,
                                      kv_int8_logit_tolerance)
    import jax.numpy as jnp

    rng = np.random.default_rng(11)
    prompt = rng.integers(0, tiny_cfg.vocab_size, 72)
    # only the 48-token ancestor of the 64-token ask is registered
    cluster = _live_cluster(donor_kv, [prompt[:48]])
    logits, ref_logits = LogitsRecorder(), LogitsRecorder()
    eng = LiveEngine(tiny_params, tiny_cfg, cluster, resolution="240p",
                     on_logits=logits)
    req = eng.submit(prompt, reuse_prefix="by-tokens", reuse_tokens=64,
                     max_new_tokens=4)
    eng.run()
    assert req.storage_hit == "partial"
    assert req.reuse_tokens == 48 and req.requested_reuse_tokens == 64
    assert cluster.partial_hits == 1

    ref = LiveEngine(tiny_params, tiny_cfg, KVStore(), resolution="240p",
                     on_logits=ref_logits)
    ref_req = ref.submit(prompt, max_new_tokens=4)
    ref.run()
    check_streams(logits[req.rid], eng.outputs[req.rid],
                  ref_logits[ref_req.rid], ref.outputs[ref_req.rid],
                  kv_int8_logit_tolerance(tiny_cfg.num_layers, jnp.float32),
                  "partial hit vs full recompute")


def test_live_miss_falls_back_to_full_prefill(tiny_cfg, tiny_params,
                                              donor_kv):
    from repro.serving.engine import LiveEngine

    rng = np.random.default_rng(12)
    prompt = rng.integers(0, tiny_cfg.vocab_size, 40)
    other = rng.integers(0, tiny_cfg.vocab_size, 32)
    cluster = _live_cluster(donor_kv, [other])
    eng = LiveEngine(tiny_params, tiny_cfg, cluster, resolution="240p")
    req = eng.submit(prompt, reuse_prefix="by-tokens", reuse_tokens=32,
                     max_new_tokens=4)
    eng.run()
    assert req.storage_hit == "miss" and req.reuse_tokens == 0
    assert len(eng.outputs[req.rid]) == 4

    ref = LiveEngine(tiny_params, tiny_cfg, KVStore(), resolution="240p")
    ref_req = ref.submit(prompt, max_new_tokens=4)
    ref.run()
    assert eng.outputs[req.rid] == ref.outputs[ref_req.rid]


def test_live_engine_fail_node_miss_heal_cycle(tiny_cfg, tiny_params,
                                               donor_kv):
    """Wall-clock engine + manual heal: a node failure turns the next
    ask into a miss (token-identical full-prefill fallback), the
    delayed write-on-miss restores residency after the recompute, and
    pump_heal() drains the queued re-replication without duplicating
    copies that already came back."""
    from repro.serving.engine import LiveEngine

    rng = np.random.default_rng(21)
    prefix = rng.integers(0, tiny_cfg.vocab_size, 48)
    suffix = rng.integers(0, tiny_cfg.vocab_size, 8)
    prompt = np.concatenate([prefix, suffix])
    cluster = _live_cluster(donor_kv, [prefix], n_nodes=2,
                            heal="manual")
    eng = LiveEngine(tiny_params, tiny_cfg, cluster, resolution="240p")
    r0 = eng.submit(prompt, reuse_prefix="by-tokens", reuse_tokens=48,
                    max_new_tokens=4)
    eng.run()
    assert r0.storage_hit == "full"
    holder = r0.storage_node
    eng.fail_node(holder)
    assert cluster.heal_queue  # re-replication queued, not teleported
    r1 = eng.submit(prompt, reuse_prefix="by-tokens", reuse_tokens=48,
                    max_new_tokens=4)
    eng.run()
    assert r1.storage_hit == "miss" and r1.reuse_tokens == 0
    ref = LiveEngine(tiny_params, tiny_cfg, KVStore(), resolution="240p")
    ref_req = ref.submit(prompt, max_new_tokens=4)
    ref.run()
    assert eng.outputs[r1.rid] == ref.outputs[ref_req.rid]
    # delayed write-on-miss already restored residency on a live node
    r2 = eng.submit(prompt, reuse_prefix="by-tokens", reuse_tokens=48,
                    max_new_tokens=4)
    eng.run()
    assert r2.storage_hit == "full" and r2.storage_node != holder
    assert eng.outputs[r2.rid] == ref.outputs[ref_req.rid]
    key = next(iter(cluster.catalog))
    cluster.pump_heal(eng.now())  # no-op: the copy is already back
    assert sum(1 for n in cluster.nodes if n.contains(key)) == 1


@pytest.mark.slow
def test_cross_env_hit_miss_evict_sequences_agree(tiny_cfg, tiny_params,
                                                  donor_kv):
    """Simulator and LiveEngine drive identically-configured clusters
    through the same access order and must log the identical
    admit/evict/hit/partial/miss event sequence."""
    from repro.cluster.simulator import MethodSpec, ServingSimulator
    from repro.serving.engine import LiveEngine

    rng = np.random.default_rng(5)
    base = rng.integers(0, tiny_cfg.vocab_size, 48)
    other = rng.integers(0, tiny_cfg.vocab_size, 32)
    tok_a, tok_b, tok_c = base[:32], base[:48], other

    # live side: real manifests, capacity fits 2 of the 3 entries
    sizes = {}
    probe = _live_cluster(donor_kv, [tok_a, tok_b, tok_c])
    for key, e in probe.catalog.items():
        sizes[key] = e.stored_bytes
    cap = int(sorted(sizes.values())[-1] + sorted(sizes.values())[-2] + 1)
    live = StorageCluster([StorageNode("n0", capacity_bytes=cap,
                                       policy="lru")])
    for toks in (tok_a, tok_b, tok_c):
        kv_k, kv_v = donor_kv(toks)
        live.register_prefix(toks, kv_k, kv_v, tokens_per_chunk=16,
                             resolutions=("240p",))
    keys = list(live.catalog)  # registration order: a, b, c
    eng = LiveEngine(tiny_params, tiny_cfg, live, resolution="240p")
    suffix = rng.integers(0, tiny_cfg.vocab_size, 8)
    # access order: c (hit), a (likely evicted), b, c — write-on-miss
    # re-admissions keep the pressure on
    for toks in (tok_c, tok_a, tok_b, tok_c):
        eng.submit(np.concatenate([toks, suffix]),
                   reuse_prefix="by-tokens", reuse_tokens=len(toks),
                   max_new_tokens=2)
        eng.run()

    # simulator side: synthetic entries with the live sizes and parents
    sim_nodes = [StorageNode("n0", capacity_bytes=cap, policy="lru")]
    sim_cluster = StorageCluster(sim_nodes)
    for key in keys:
        src = live.catalog[key]
        sim_cluster.register(StoredPrefix(
            key=key, n_tokens=src.n_tokens,
            bytes_by_resolution={"240p": src.stored_bytes},
            raw_kv_bytes=src.raw_kv_bytes, parent=src.parent), 0.0)
    key_of = {len(tok_a): keys[0], len(tok_b): keys[1]}
    order = [keys[2], keys[0], keys[1], keys[2]]
    lens = [len(tok_c), len(tok_a), len(tok_b), len(tok_c)]
    reqs = [Request(rid=i, arrival=(i + 1) * 50.0,
                    prompt_len=lens[i] + 8, reuse_tokens=lens[i],
                    prefix=order[i], max_new_tokens=2)
            for i in range(4)]
    spec = MethodSpec("kvfetcher", ratios={"stream": 8.0}, adaptive=False,
                      fixed_resolution="240p", uses_decode_pool=False)
    sim = ServingSimulator(tiny_cfg, spec,
                           bandwidth=BandwidthTrace.constant(0.01),
                           storage=sim_cluster, chunk_tokens=16)
    sim.run(reqs, max_new_tokens=2)

    assert live.events == sim_cluster.events
    kinds = [e[0] for e in live.events]
    assert "miss" in kinds and "evict" in kinds, \
        "sequence exercised no pressure; test is vacuous"
    assert key_of  # silence unused (kept for debugging readability)


@pytest.mark.slow
def test_cross_env_churn_fail_heal_expire_reject_agree(tiny_cfg,
                                                       tiny_params,
                                                       donor_kv):
    """ISSUE 4 acceptance: a seeded churn trace — admission rejections,
    TTL expiry, a node failure mid-trace, the sync ring heal, and the
    post-recovery re-balance — must replay the identical
    fail/heal/expire/reject/recover/rebalance event sequence in the
    live engine (real manifests, wall clock) and the analytic simulator
    (synthetic entries, virtual clock)."""
    from repro.cluster.simulator import MethodSpec, ServingSimulator
    from repro.serving.engine import LiveEngine

    rng = np.random.default_rng(9)
    tok_a = rng.integers(0, tiny_cfg.vocab_size, 32)  # ttl=0: expires
    tok_b = rng.integers(0, tiny_cfg.vocab_size, 40)  # fail/heal target
    suffix = rng.integers(0, tiny_cfg.vocab_size, 8)

    def build_live():
        nodes = [StorageNode(f"n{i}") for i in range(2)]
        c = StorageCluster(nodes, replication=1, heal="sync",
                           admission="second_hit", admission_min_asks=1)
        for toks, ttl in ((tok_a, 0.0), (tok_b, None)):
            kv_k, kv_v = donor_kv(toks)
            c.register_prefix(toks, kv_k, kv_v, tokens_per_chunk=16,
                              resolutions=("240p",), ttl=ttl)
        return c

    live = build_live()
    keys = list(live.catalog)  # [key_a, key_b] in registration order
    eng = LiveEngine(tiny_params, tiny_cfg, live, resolution="240p")
    # access script: a (miss->admit), a (expire->miss->admit),
    # b (miss->admit), FAIL b's holder, b (miss or heal-hit), a again
    order = [tok_a, tok_a, tok_b, None, tok_b, tok_a]
    failed = None
    for toks in order:
        if toks is None:
            failed = next(n.node_id for n in live.nodes
                          if n.contains(keys[1]))
            eng.fail_node(failed)
            continue
        eng.submit(np.concatenate([toks, suffix]),
                   reuse_prefix="by-tokens", reuse_tokens=len(toks),
                   max_new_tokens=2)
        eng.run()
    # the failed holder comes back after the trace: recovery must
    # re-balance keys whose ring home it is back onto it (and trim the
    # surplus copy off the heal survivor)
    eng.recover_node(failed)

    # simulator side: synthetic twins under the same churn, same keys
    sim_nodes = [StorageNode(f"n{i}") for i in range(2)]
    sim_cluster = StorageCluster(sim_nodes, replication=1, heal="sync",
                                 admission="second_hit",
                                 admission_min_asks=1)
    for key in keys:
        src = live.catalog[key]
        sim_cluster.register(StoredPrefix(
            key=key, n_tokens=src.n_tokens,
            bytes_by_resolution={"240p": src.stored_bytes},
            raw_kv_bytes=src.raw_kv_bytes, parent=src.parent,
            ttl=src.ttl, pinned=src.pinned), 0.0)
    # nothing is resident at registration under second_hit admission;
    # the recompute admits b onto its ring primary — same ring, same
    # node id in both environments
    sim_holder = sim_cluster.primary_node(keys[1]).node_id
    lens = {id(tok_a): (len(tok_a), keys[0]),
            id(tok_b): (len(tok_b), keys[1])}
    reqs = []
    t_fail = None
    t = 50.0
    for toks in order:
        if toks is None:
            t_fail = t - 25.0  # between the two neighbouring arrivals
            continue
        n_tok, key = lens[id(toks)]
        reqs.append(Request(rid=len(reqs), arrival=t,
                            prompt_len=n_tok + 8, reuse_tokens=n_tok,
                            prefix=key, max_new_tokens=2))
        t += 50.0
    spec = MethodSpec("kvfetcher", ratios={"stream": 8.0}, adaptive=False,
                      fixed_resolution="240p", uses_decode_pool=False)
    sim = ServingSimulator(tiny_cfg, spec,
                           bandwidth=BandwidthTrace.constant(0.01),
                           storage=sim_cluster, chunk_tokens=16,
                           fail_at=[(t_fail, sim_holder)],
                           recover_at=[(t + 25.0, sim_holder)])
    sim.run(reqs, max_new_tokens=2)

    assert live.events == sim_cluster.events
    kinds = [e[0] for e in live.events]
    for needed in ("fail", "heal", "expire", "reject", "miss", "admit",
                   "recover", "rebalance"):
        assert needed in kinds, f"churn trace exercised no {needed!r}"
    # the re-balance pulled b home onto its recovered ring primary and
    # dropped the surplus copy, so replication=1 holds again
    assert ("rebalance", keys[1], sim_holder) in sim_cluster.events
    assert sum(n.contains(keys[1]) for n in live.nodes) == 1
    assert live.primary_node(keys[1]).contains(keys[1])


# ---------------------------------------------------------------------------
# per-resolution eviction (ISSUE 7): a StoredPrefix holds multiple encoded
# resolutions and capacity pressure evicts cold rungs, not whole prefixes
# ---------------------------------------------------------------------------

def _ladder(key, rungs, parent=None):
    return StoredPrefix(key=key, n_tokens=1000, bytes_by_resolution=rungs,
                        raw_kv_bytes=8 * sum(rungs.values()), parent=parent)


def test_resolution_granularity_evicts_cold_rung_keeps_prefix():
    n = StorageNode("n0", capacity_bytes=50 * MB, policy="lru",
                    evict_granularity="resolution")
    n.put(_ladder("a", {"240p": 10 * MB, "1080p": 30 * MB}), 0.0)
    n.note_resolution_use("a", "1080p")  # the rung the fetch path uses
    ok, evicted = n.put(_ladder("b", {"240p": 15 * MB}), 1.0)
    assert ok and evicted == ["a/240p"]  # cold rung goes, prefix stays
    assert n.contains("a")
    assert n.resident_resolutions("a") == ("1080p",)
    assert n.used_bytes == 45 * MB
    assert n.bytes_by_resolution["240p"] == 15 * MB


def test_resolution_granularity_last_rung_drops_whole_prefix():
    n = StorageNode("n0", capacity_bytes=40 * MB,
                    evict_granularity="resolution")
    n.put(_ladder("a", {"1080p": 30 * MB}), 0.0)
    ok, evicted = n.put(_ladder("b", {"240p": 20 * MB}), 1.0)
    assert ok and evicted == ["a"]  # plain key: the whole prefix went
    assert not n.contains("a")
    assert n.resident_resolutions("a") is None


def test_note_resolution_use_steers_lfu_victim():
    """Per-rung frequency from the fetch path decides which rung
    survives: the rung the adaptive selector keeps delivering outlives
    a bigger, barely-used one."""
    n = StorageNode("n0", capacity_bytes=40 * MB, policy="lfu",
                    evict_granularity="resolution")
    n.put(_ladder("a", {"240p": 10 * MB, "1080p": 20 * MB}), 0.0)
    for _ in range(3):
        n.note_resolution_use("a", "240p")
    n.note_resolution_use("a", "1080p")  # more recent but less frequent
    _, evicted = n.put(_ladder("b", {"240p": 15 * MB}), 1.0)
    assert evicted == ["a/1080p"]
    assert n.resident_resolutions("a") == ("240p",)


def test_readmission_restores_full_ladder_and_keeps_rung_history():
    n = StorageNode("n0", capacity_bytes=50 * MB,
                    evict_granularity="resolution")
    e = _ladder("a", {"240p": 10 * MB, "1080p": 30 * MB})
    n.put(e, 0.0)
    n.note_resolution_use("a", "1080p")
    n.put(_ladder("b", {"240p": 15 * MB}), 1.0)  # evicts a/240p
    assert n.resident_resolutions("a") == ("1080p",)
    n.put(_ladder("x", {"240p": 1 * MB}), 1.5)  # headroom stays
    ok, evicted = n.put(e, 2.0)  # re-register: the 240p rung returns
    # cold single-rung "b" (oldest untouched) is the victim, and losing
    # its last rung drops the whole prefix
    assert ok and evicted == ["b"]
    assert n.resident_resolutions("a") == ("240p", "1080p")
    assert n.residents["a"].res_hits == {"1080p": 1}  # history kept


def test_cluster_rung_eviction_narrows_hit_resolutions():
    """The surviving rung set travels on StorageHit.resolutions (the
    fetch controller caps its ladder with it), and rung evictions are
    logged as distinct `evict_res` events."""
    node = StorageNode("n0", capacity_bytes=50 * MB, policy="lru",
                       evict_granularity="resolution")
    c = StorageCluster([node])
    c.register(_ladder("a", {"240p": 10 * MB, "1080p": 30 * MB}), 0.0)
    hit = c.lookup("a", 1.0)
    assert hit.kind == "full"
    assert hit.resolutions == ("240p", "1080p")  # ladder order
    c.note_resolution_use("n0", "a", "1080p")  # res_sink feedback
    c.register(_ladder("b", {"240p": 15 * MB}), 2.0)
    assert ("evict_res", "a/240p", "n0") in c.events
    assert not any(ev[0] == "evict" for ev in c.events)
    hit = c.lookup("a", 3.0)
    assert hit.kind == "full" and hit.resolutions == ("1080p",)
    # dead-node / unknown-key feedback is a safe no-op
    c.note_resolution_use("n9", "a", "1080p")
    c.note_resolution_use("n0", "nope", "1080p")
